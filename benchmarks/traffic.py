"""The one traffic generator: a data file of parameters -> a schedule.

Every run of a cell sends the SAME multiset of requests; ``--seed`` only
decides their order. Lengths are the quantiles of a clipped lognormal at
(i + 1/2) / n, inter-arrival gaps the quantiles of an exponential at the
same points, scaled so that they sum to the phase's length. The ramp and
the window are multisets of their own, each permuted within itself, so
the requests due inside the window are the same set on every seed. Both
loops permute in strata (``block``), so that every stretch of a run
carries nearly the same load on every seed, and not only the run.

Stdlib only: the load generator imports this and must never import JAX.

A traffic file (``benchmarks/traffic/<mix>.json``) holds:

  loop            "open" (independent users, a schedule of due times) or
                  "closed" (``clients`` callers, each sending its next
                  request when the last one ended)
  rate_req_s      open loop: offered requests per second
  clients         closed loop: concurrent callers
  ramp_s          seconds of the same traffic before the window opens
                  (warm-up, not part of ``--seconds``)
  block           open loop: the phase's multiset is dealt into consecutive
                  strata of about ``block`` requests (12 is two seconds at
                  6 req/s), each taking lengths and gaps from across the
                  whole distribution so that the strata's sums of prompt
                  tokens, of output tokens and of gaps agree; the seed
                  permutes within a stratum and the order of the strata
                  (one stratum as long as the phase lets long prompts
                  clump in one run and spread in another)
  prompt_tokens,
  output_tokens   {"median", "sigma", "min", "max"} of a clipped lognormal
  block, blocks   closed loop: the list is ``blocks`` copies of one
                  ``block``-sized multiset, each copy permuted by itself,
                  so any prefix a run consumes is nearly the same multiset.
                  The callers' first requests are a multiset of their own
                  (phase "ramp") with output lengths cut to (j + 1/2) /
                  clients of a quantile, so the slots start out of step
                  instead of finishing together

A mix is held to the cell it is paired with, not to one length for all:
every request of the list must get its whole output from a cache of that
cell's configuration's ``max_seq``, prompt + output < ``max_seq``
(``tests/test_benchmark.py``), and a traffic file that no cell uses fails.

These two loops are all it builds. Arrivals in bursts, sessions of several
turns, prompts that share a prefix, a replay of recorded requests: each
needs a new branch here and in loadgen.py, which only a PR that defines
the benchmark may add (benchmarks/README.md).
"""

from __future__ import annotations

import json
import math
import random
from statistics import NormalDist


def lognormal_quantiles(spec: dict, n: int) -> list[int]:
    """n whole numbers: the (i + 1/2) / n quantiles of the lognormal with
    this median and sigma, clipped to [min, max]. Sorted ascending."""
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    z = NormalDist().inv_cdf
    return [int(min(spec["max"], max(spec["min"],
                                     round(math.exp(mu + sigma * z((i + 0.5) / n))))))
            for i in range(n)]


def exponential_gaps(n: int, total_s: float) -> list[float]:
    """n gaps, the (i + 1/2) / n quantiles of an exponential, scaled so
    that they sum to ``total_s`` exactly. Sorted ascending."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = total_s / sum(raw)
    return [g * k for g in raw]


def _permuted(values: list, seed: int, what: str) -> list:
    out = list(values)
    random.Random(f"{seed}/{what}").shuffle(out)
    return out


def strata(values: list, block: int) -> list[list]:
    """``values`` dealt into ``len(values) // block`` strata of ``block``
    values, or one more where they do not divide, with sums as equal as
    the greedy deal makes them: largest first, each to the stratum that
    holds least and still has room. A stratum that drew from the tail is
    filled up from the small end, so each holds values from across the
    whole distribution (an exponential's longest gap is three quarters of
    a stratum by itself: a deal in plain rounds cannot balance it)."""
    n_strata = max(1, len(values) // block)
    base, extra = divmod(len(values), n_strata)
    room = [base + (s < extra) for s in range(n_strata)]
    out: list[list] = [[] for _ in range(n_strata)]
    sums = [0.0] * n_strata
    for v in sorted(values, reverse=True):
        s = min((s for s in range(n_strata) if len(out[s]) < room[s]),
                key=lambda s: (sums[s], s))
        out[s].append(v)
        sums[s] += v
    return out


def _dealt(values: list, block: int, seed: int, what: str) -> list:
    """The phase's order of one quantity: its strata in an order drawn
    from the seed, each permuted by itself (a ``block`` as long as the
    phase makes one stratum: the whole phase permuted)."""
    return [v for s, stratum in enumerate(_permuted(strata(values, block),
                                                    seed, what))
            for v in _permuted(stratum, seed, f"{what}/{s}")]


def _open_phase(params: dict, seed: int, length_s: float, phase: str,
                t0: float) -> list[dict]:
    n = max(1, round(params["rate_req_s"] * length_s))
    block = int(params["block"])
    gaps = _dealt(exponential_gaps(n, length_s), block, seed,
                  phase + "/gaps")
    prompts = _dealt(lognormal_quantiles(params["prompt_tokens"], n), block,
                     seed, phase + "/prompts")
    outputs = _dealt(lognormal_quantiles(params["output_tokens"], n), block,
                     seed, phase + "/outputs")
    reqs, t = [], t0
    for g, p, o in zip(gaps, prompts, outputs):
        t += g
        reqs.append({"phase": phase, "due": t, "prompt": p, "output": o})
    return reqs


def build(params: dict, seed: int, seconds: float) -> dict:
    """The schedule of one run. Due times are seconds relative to the
    window's opening (the ramp's are negative). A closed loop has no due
    times: ``requests`` is the list its callers draw from in order."""
    loop = params["loop"]
    ramp_s = float(params.get("ramp_s", 0.0))
    if loop == "open":
        reqs = ((_open_phase(params, seed, ramp_s, "ramp", -ramp_s)
                 if ramp_s > 0 else [])
                + _open_phase(params, seed, float(seconds), "window", 0.0))
    elif loop == "closed":
        block, blocks = int(params["block"]), int(params["blocks"])
        prompts = lognormal_quantiles(params["prompt_tokens"], block)
        outputs = lognormal_quantiles(params["output_tokens"], block)
        # the callers' first requests: a multiset of its own, the output
        # quantiles in a fixed order cut to (j + 1/2) / clients
        clients = int(params["clients"])
        first = _permuted(lognormal_quantiles(params["output_tokens"],
                                              clients), 0, "stagger")
        first = [max(1, round(o * (j + 0.5) / clients))
                 for j, o in enumerate(first)]
        reqs = [{"phase": "ramp", "due": None, "prompt": p, "output": o}
                for p, o in zip(
                    _permuted(lognormal_quantiles(params["prompt_tokens"],
                                                  clients), seed,
                              "ramp/prompts"),
                    _permuted(first, seed, "ramp/outputs"))]
        for b in range(blocks):
            for p, o in zip(_permuted(prompts, seed, f"block{b}/prompts"),
                            _permuted(outputs, seed, f"block{b}/outputs")):
                reqs.append({"phase": "closed", "due": None,
                             "prompt": p, "output": o})
    else:
        raise ValueError(f"unknown loop kind {loop!r}")
    for i, r in enumerate(reqs):
        r["idx"] = i
    return {"loop": loop, "ramp_s": ramp_s, "seconds": float(seconds),
            "clients": int(params.get("clients", 0)), "requests": reqs}


def scaled(params: dict, length_scale: float) -> dict:
    """The same mix with every length multiplied by ``length_scale``: the
    CPU rehearsal's cut (a tiny preset holds 128 positions)."""
    out = dict(params)
    for key in ("prompt_tokens", "output_tokens"):
        spec = dict(params[key])
        for k in ("median", "min", "max"):
            spec[k] = max(2, int(spec[k] * length_scale))
        out[key] = spec
    return out


def load(path: str, overrides: dict | None = None) -> dict:
    with open(path) as f:
        params = json.load(f)
    for k, v in (overrides or {}).items():
        params[k] = v
    return params


def prompt_ids(seed: int, idx: int, n: int, vocab: int) -> list[int]:
    """Request ``idx``'s prompt: n token ids in [1, vocab) from the seed.
    Distinct per request, so no two prompts share a prefix."""
    return random.Random(f"{seed}/prompt/{idx}").choices(range(1, vocab), k=n)
