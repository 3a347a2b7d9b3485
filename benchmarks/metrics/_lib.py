"""What the metric readers share. A reader is ``read(ctx) -> float | None``
in ``benchmarks/metrics/<metric name>.py``; ``ctx`` is what run.py
gathered in one run (see its SimpleNamespace): the client's per-request
samples (times in seconds relative to the window's opening), the
program's timeline events inside the window, the reduced device trace,
compile counts, /metrics at the window's ends, memory, model sizes.
None means "nothing to read here": the metric is left out of the line.
"""

from __future__ import annotations


def pct(values, q: float) -> float | None:
    """The q-th percentile by linear interpolation between order
    statistics."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def ttft_ms(ctx) -> list[float]:
    """Due time to first token at the client, every request due in the
    window (a closed loop has no due time: sent to first token)."""
    return [(s["first"] - s.get("due", s["sent"])) * 1e3
            for s in ctx.window if "first" in s]


def tpot_ms(ctx) -> list[float]:
    """Per request, (last - first token) / (tokens - 1)."""
    return [(s["last"] - s["first"]) / (s["n"] - 1) * 1e3
            for s in ctx.window if s.get("n", 0) >= 2]


def module_time(ctx, *needles: str) -> tuple[int, float]:
    """(executions, seconds) of the compiled programs whose name holds one
    of ``needles``, on the first device of the traced window."""
    if not ctx.trace or "modules" not in ctx.trace:
        return 0, 0.0
    count, seconds = 0, 0.0
    for name, m in ctx.trace["modules"].items():
        if any(n in name for n in needles):
            count += m["count"]
            seconds += m["seconds"]
    return count, seconds


# the program's jitted functions (tpu/generator.py:_build_jits); a
# `tracing` PR that gives them named scopes changes these, not the readers
DECODE_PROGRAMS = ("_step_fn",)
# (the chunk programs are jitted partials, which XLA names jit__unknown)
PREFILL_PROGRAMS = ("_prefill_fn", "_chunk_mid", "_chunk_final", "jit__unknown")


def decode_step_s(ctx) -> float | None:
    """Device seconds of one decode step: the fused decode block's time
    over the steps it holds."""
    count, seconds = module_time(ctx, *DECODE_PROGRAMS)
    if not count:
        return None
    return seconds / (count * ctx.decode_block)


def live_tokens(ctx, t: float) -> float:
    """Tokens whose KV the decode step reads at ``t`` (seconds from the
    window's opening): for every request streaming then, its prompt and
    the share of its output that had arrived."""
    live = 0.0
    for s in ctx.samples:
        if "first" in s and s["first"] <= t <= s["last"]:
            done = (t - s["first"]) / max(s["last"] - s["first"], 1e-9)
            live += s["prompt"] + s["n"] * done
    return live


def trace_mid(ctx) -> float | None:
    """The middle of the traced seconds, from the window's opening."""
    if not ctx.trace or "span" not in ctx.trace:
        return None
    a, b = ctx.trace["span"]
    return (a + b) / 2 - ctx.t_open


def events(ctx, kind: str, span=None) -> list[tuple]:
    """The program's timeline events of one kind inside the window, or
    inside ``span`` (monotonic start, stop)."""
    return [e for e in ctx.timeline if e[3] == kind
            and (span is None or span[0] <= e[1] < span[1])]
