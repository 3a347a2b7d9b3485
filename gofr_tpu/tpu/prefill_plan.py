"""Which programs a prompt within the chunk budget runs in.

The engine pads a prompt to a bucket of its lattice, so a prompt just
past a bucket runs almost twice its positions. It may instead run as TWO
dispatches of programs the engine already has: a whole bucket ``first``
through the prefill program, then the rest through the final-chunk
program at the bucket ``rest`` that holds it. Whether that is cheaper
depends on the chip and the model (a program bound by its weight stream
costs the same whatever it holds), so the choice is made from a table of
seconds a program, which the engine measures at the end of its warm-up
(``GenerationEngine._time_prefills``). Nothing here names a width, a
chip or a model."""

from __future__ import annotations

from typing import Mapping, Sequence

from .batcher import pad_bucket

# seconds a bucket: the two timings of its program
Timings = Mapping[int, Sequence[float]]


def first_buckets(buckets: Sequence[int], chunk: int, prefill_s: Timings,
                  final_s: Timings, *, overlapped: bool,
                  max_seq: int) -> list[int]:
    """``first[L]`` for every prompt length up to ``chunk``: the bucket
    the first of two dispatches runs, or 0 where the prompt stays in one
    bucket. A program's cost is the smaller of its two timings (what
    disturbs a timing makes it longer); a split is taken only where it
    beats the one bucket by more than that bucket's two timings differ,
    so a tie, or a table too noisy to tell, stays one bucket.

    ``overlapped``: the rest ends at the prompt's end and computes the
    first part's last rows again (a family whose rows can be), so it
    may not be wider than the prompt; otherwise it starts where the
    first part ended and is padded, and has to end inside ``max_seq``."""
    first = [0] * (chunk + 1)
    cost = {b: min(t) for b, t in prefill_s.items()}
    cost_rest = {b: min(t) for b, t in final_s.items()}
    for L in range(1, chunk + 1):
        one = pad_bucket(L, buckets)
        if one not in cost:
            continue
        best, b_best = None, 0
        for b1 in buckets:
            if b1 >= L:
                break
            rest = pad_bucket(L - b1, buckets)
            if b1 not in cost or rest not in cost_rest:
                continue
            if (rest > L) if overlapped else (b1 + rest > max_seq):
                continue
            # of two splits that cost the same, the larger first part
            if best is None or cost[b1] + cost_rest[rest] <= best:
                best, b_best = cost[b1] + cost_rest[rest], b1
        noise = abs(prefill_s[one][0] - prefill_s[one][-1])
        if best is not None and best < cost[one] - noise:
            first[L] = b_best
    return first


def ranges(first: Sequence[int], buckets: Sequence[int]) -> dict[int, list]:
    """The plan as ``stats()`` shows it: for each bucket that holds a
    split prompt, the runs of lengths ``{"from", "to", "first", "rest"}``
    that split and into what; a bucket no prompt leaves is not listed."""
    out: dict[int, list] = {}
    for L, b1 in enumerate(first):
        if not b1:
            continue
        rest = pad_bucket(L - b1, buckets)
        runs = out.setdefault(pad_bucket(L, buckets), [])
        last = runs[-1] if runs else None
        if last and last["to"] == L - 1 and (last["first"], last["rest"]) \
                == (b1, rest):
            last["to"] = L
        else:
            runs.append({"from": L, "to": L, "first": b1, "rest": rest})
    return out
