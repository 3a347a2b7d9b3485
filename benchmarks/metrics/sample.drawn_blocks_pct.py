"""Decode blocks of the window that took the sampler's drawn branch, as a
share of the window's decode blocks. The program counts, at each dispatch
and from its own arrays, whether an active slot has ``temperature > 0``
(``generator._sampling_flag``): the running totals are
``stats()["sampling"]`` (``drawn_blocks`` over ``blocks``, since the
program's start, warm-up and ramp included), and the ``decode`` event of a
block that drew carries the flag after the ring rows (bit 0: a slot draws,
bit 1: from its top-k; an all-greedy block's event carries none), which is
what this reads, over the window. 0.0 says that every step of the window
took an argmax and a logprob and skipped the top-64 and the vocabulary-wide
draw. The device's predicate follows the carried ``active`` mask, so a slot
that stops inside a block already dispatched may count here for the block
behind it, where the device skips it. A program without the counter (the
parent of the PR that brought it) gives None."""
from benchmarks.metrics._lib import events

FLAG = 12   # (seq, t0, dur, kind, slots, steps, live, fetched,
#              assigned, touched, states, ring, sampled)


def read(ctx):
    if "sampling" not in (getattr(ctx, "engine_stats", None) or {}):
        return None
    blocks = events(ctx, "decode")
    if not blocks:
        return None
    drew = sum(1 for e in blocks if len(e) > FLAG and (e[FLAG] or 0) & 1)
    return 100.0 * drew / len(blocks)
