"""What the sparse-latent family's readers share: the decode blocks'
counts of live rows, ring rows and rows kept from the program's timeline,
its three decode kernels' names in a device trace. The expert layer is
``deepseek_v3``'s and so is its readers' arithmetic (``_deepseek_v3``:
imported, not copied)."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._deepseek_v3 import (  # noqa: F401
    expert_seconds, moe_blocks, op_seconds, per_step_mean, traced_steps)
from benchmarks.metrics._lib import events

# the names the device trace gives the kernels (their jitted functions):
# ops/mla.py's walk over the rows a selection kept and over a ring,
# ops/dsa.py's score pass over the index keys
KEPT_KERNEL = "decode_attention_kept"
RING_KERNEL = "decode_attention_ring"
SCORE_KERNEL = "index_scores_stacked"


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return bool(ctx.model.get("index_topk")) \
        and "window" in (ctx.model.get("layer_pattern") or ())


def _span(ctx):
    return ctx.trace.get("span") if ctx.trace else None


def kept_blocks(ctx, span=None):
    """Decode events that carry the counts of the selection: (duration,
    steps, live rows of a full layer at dispatch, rows of a window
    layer's rings at dispatch, rows the block's steps kept over the full
    layers, rows they chose among)."""
    return [(e[2], e[5], e[6], e[11], *e[13])
            for e in events(ctx, "decode", span)
            if len(e) > 13 and e[13] is not None and e[11] is not None]


def _blocks(ctx, traced: bool):
    if not is_family(ctx):
        return []
    return (kept_blocks(ctx, _span(ctx)) if traced else None) \
        or kept_blocks(ctx)


def rows_mean(ctx, field: int, traced: bool):
    """Rows a step (2: a full layer's live rows, 3: a window layer's ring
    rows, both at dispatch) averaged by duration over the traced seconds'
    blocks or over the window's."""
    blocks = _blocks(ctx, traced)
    total = sum(b[0] for b in blocks)
    return sum(b[field] * b[0] for b in blocks) / total if total > 0 \
        else None


def kept_mean(ctx, traced: bool):
    """Rows ONE full layer's selection kept in ONE step, over the slots:
    the blocks' counts over their steps and the full layers."""
    blocks = _blocks(ctx, traced)
    steps = sum(b[1] for b in blocks)
    if not steps:
        return None
    return sum(b[4] for b in blocks) / steps / rf.kinds(ctx.model)["full"]


def kept_share(ctx):
    """Rows kept over rows chosen among, over the window's decode
    blocks: the program's two counts, so 1 exactly while no context
    passes ``index_topk``."""
    blocks = _blocks(ctx, False)
    among = sum(b[5] for b in blocks)
    return sum(b[4] for b in blocks) / among if among else None


def kernel_ms(ctx, name: str):
    """Device time of the kernel ``name`` (all its layers) in one decode
    step, from the traced seconds."""
    if not is_family(ctx):
        return None
    steps = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: name in n)
    return s / steps * 1e3 if steps and s > 0 else None
