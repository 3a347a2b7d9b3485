"""What the window family's readers share: the decode blocks' counts of
ring rows from the program's timeline, the two decode kernels' names in a
device trace. The expert layer is ``deepseek_v3``'s and so is its
readers' arithmetic (``_deepseek_v3``: imported, not copied)."""
from benchmarks.metrics._deepseek_v3 import (  # noqa: F401
    expert_seconds, live_rows, moe_blocks, op_seconds, per_step_mean,
    traced_steps)
from benchmarks.metrics._lib import events

# the names the device trace gives ops/flash_decode.py's kernel (its
# jitted functions): over a window layer's ring, over a full layer's rows
RING_KERNEL = "flash_decode_ring"
FULL_KERNEL = "flash_decode_stacked"


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return "window" in (ctx.model.get("layer_pattern") or ())


def _span(ctx):
    return ctx.trace.get("span") if ctx.trace else None


def ring_blocks(ctx, span=None):
    """Decode events that carry the count of ring rows: (duration, steps,
    live rows of a full layer, rows of a window layer's rings)."""
    return [(e[2], e[5], e[6], e[11]) for e in events(ctx, "decode", span)
            if len(e) > 11 and e[11] is not None]


def rows_mean(ctx, field: int, traced: bool):
    """Live rows (2: a full layer's, 3: a window layer's rings) the
    decode blocks held at dispatch, averaged by duration over the traced
    seconds' blocks or over the window's."""
    blocks = (ring_blocks(ctx, _span(ctx)) if traced else None) \
        or ring_blocks(ctx)
    total = sum(b[0] for b in blocks)
    return sum(b[field] * b[0] for b in blocks) / total if total > 0 \
        else None


def kernel_ms(ctx, name: str):
    """Device time of the kernel ``name`` (all its layers) in one decode
    step, from the traced seconds."""
    if not is_family(ctx):
        return None
    steps = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: name in n)
    return s / steps * 1e3 if steps and s > 0 else None


def kernel_roofline(ctx, name: str, kind: str, field: int):
    """The kernel's share of its roofline: the larger of (live rows x
    bytes a row x layers) / bandwidth and (live rows x operations a row x
    layers) / matrix peak, over its measured time a step."""
    from benchmarks import roofline_laguna as rf

    ms, rows = kernel_ms(ctx, name), rows_mean(ctx, field, traced=True)
    if ms is None or rows is None or ctx.peaks is None:
        return None
    layers = rf.kinds(ctx.model)[kind]
    least = rf.least_seconds(
        rows * rf.row_bytes(ctx.model) * layers,
        rows * rf.attn_flops_per_row(ctx.model, kind) * layers, ctx.peaks)
    return 100.0 * least / (ms / 1e3)
