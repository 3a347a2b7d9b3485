"""What the block-diffusion family's readers share: the decode
dispatches' counts from the program's timeline (a dispatch is
``decode_block`` PASSES over the slots' blocks; its event ends in the
slot-passes it ran, the tokens it delivered and the rows it wrote), the
block-decode kernel's name in a device trace. The expert layer is
``deepseek_v3``'s and so is its readers' arithmetic (``_deepseek_v3``:
imported, not copied)."""
from benchmarks.metrics._deepseek_v3 import (  # noqa: F401
    expert_seconds, moe_blocks, op_seconds, per_step_mean, traced_steps)
from benchmarks.metrics._lib import events

# the name the device trace gives the W-row branch of
# ops/flash_decode.py's kernel (its jitted function)
BLOCK_KERNEL = "flash_decode_block"
# (seq, t0, dur, kind, slots, steps, live, fetched, assigned, touched,
#  states, ring, sampled, kept, passes)
PASSES = 14


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return bool(ctx.model.get("block_length"))


def _span(ctx):
    return ctx.trace.get("span") if ctx.trace else None


def pass_blocks(ctx, span=None):
    """Decode events that carry the passes' counts: (duration, passes in
    the dispatch, slots active, live rows at dispatch, assignments,
    (pass, layer, expert) cells touched, slot-passes run, tokens
    delivered, rows written)."""
    return [(e[2], e[5], len(e[4] or ()), e[6], e[8], e[9], *e[PASSES])
            for e in events(ctx, "decode", span)
            if len(e) > PASSES and e[PASSES] is not None]


def blocks(ctx, traced: bool):
    """The traced seconds' dispatches (the window's where none starts
    inside them), or the window's."""
    if not is_family(ctx):
        return []
    return (pass_blocks(ctx, _span(ctx)) if traced else None) \
        or pass_blocks(ctx)


def mean_by_duration(ctx, field: int, traced: bool):
    bs = blocks(ctx, traced)
    total = sum(b[0] for b in bs)
    return sum(b[field] * b[0] for b in bs) / total if total > 0 else None


def kernel_ms(ctx, name: str = BLOCK_KERNEL):
    """Device time of the kernel ``name`` (all its layers) in one pass,
    from the traced seconds."""
    if not is_family(ctx):
        return None
    passes = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: name in n)
    return s / passes * 1e3 if passes and s > 0 else None


def pass_floor_s(ctx, rf):
    """The mean floor of a pass over the traced seconds' dispatches
    (``roofline_sdar.pass_floor_s``), each dispatch's passes at its own
    counts: the slots active and their live rows at dispatch, the expert
    cells touched and assignments made a pass, the share of its passes
    in which a slot denoised (a slot that denoises brings
    ``head_rows_per_slot`` rows to the head)."""
    said = (ctx.engine_stats or {}).get("diffusion") or {}
    w, c = ctx.model["block_length"], said.get("head_rows_per_slot")
    bs = blocks(ctx, traced=True)
    passes = sum(b[1] for b in bs)
    if not passes or not c or ctx.peaks is None:
        return None
    total = 0.0
    for _, steps, slots, rows, assigned, touched, ran, _, written in bs:
        denoised = ran - written / w      # slot-passes that denoised
        total += steps * rf.pass_floor_s(
            ctx.model, ctx.peaks, slots=slots, touched=touched / steps,
            assigned=assigned / steps, rows=rows,
            head=min(1.0, denoised / steps),
            head_rows=denoised / steps * c)
    return total / passes
