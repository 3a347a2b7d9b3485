"""What the hybrid family's readers share: the decode blocks' counts of
states updated from the program's timeline, the two kernels' names in a
device trace, prompt tokens prefilled in the traced seconds. The expert
layer is ``deepseek_v3``'s and so is its readers' arithmetic
(``_deepseek_v3``: imported, not copied)."""
from benchmarks.metrics._deepseek_v3 import (  # noqa: F401
    expert_seconds, live_rows, moe_blocks, op_seconds, per_step_mean,
    traced_steps)
from benchmarks.metrics._lib import events

# the names the device trace gives ops/kda.py's kernels (their jitted
# functions)
DECODE_KERNEL = "kda_decode"
PREFILL_KERNEL = "kda_prefill"


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return "linear" in (ctx.model.get("layer_pattern") or ())


def _span(ctx):
    return ctx.trace.get("span") if ctx.trace else None


def state_blocks(ctx, span=None):
    """Decode events that carry the count of states updated: (duration,
    steps, slots decoding, live rows, states)."""
    return [(e[2], e[5], len(e[4] or ()), e[6], e[10])
            for e in events(ctx, "decode", span)
            if len(e) > 10 and e[10] is not None]


def states_per_step(ctx):
    """(layer, slot) states the traced steps updated, a step."""
    blocks = state_blocks(ctx, _span(ctx)) or state_blocks(ctx)
    steps = sum(b[1] for b in blocks)
    return sum(b[4] for b in blocks) / steps if steps else None


def kernel_seconds(ctx, name: str) -> float:
    return op_seconds(ctx, lambda n: name in n)


def prefilled_tokens(ctx):
    """Positions the traced seconds' prefill programs ran through the
    kernel, padding included (it runs the bucket): for each ``prefill``
    event (one an admission, with its prompt's length) that starts inside
    the span, the whole chunks the lattice ran and the bucket that held
    the rest, by the engine's own buckets and chunk size."""
    span = _span(ctx)
    stats = ctx.engine_stats or {}
    buckets = sorted(stats.get("prompt_buckets") or ())
    chunk = (stats.get("scheduler") or {}).get("prefill_chunk")
    if span is None or not buckets or not chunk:
        return None
    total = 0
    for e in events(ctx, "prefill", span):
        whole, rest = divmod(e[5], chunk)
        if whole and not rest:
            whole, rest = whole - 1, chunk
        total += whole * chunk + next(b for b in buckets if b >= rest)
    return total or None
