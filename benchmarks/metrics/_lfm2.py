"""What the conv family's readers share: the decode blocks' counts from
the program's timeline (live rows of a full layer, tails moved), the
decode kernel's name in a device trace, the shapes that only the conv
operator's decode operations have. The expert layer is ``deepseek_v3``'s
and so is its readers' arithmetic (``_deepseek_v3``: imported, not
copied)."""
import re

from benchmarks.metrics._deepseek_v3 import (  # noqa: F401
    expert_seconds, live_rows, moe_blocks, op_seconds, per_step_mean,
    traced_steps)
# the tails moved ride in the decode events' place for states updated:
# (duration, steps, slots decoding, live rows of a full layer, tails moved)
from benchmarks.metrics._solar_open2 import state_blocks as tail_blocks

# the name the device trace gives ops/flash_decode.py's kernel (its
# jitted function) over the full layers' rows, two KV heads of 64 a row
DECODE_KERNEL = "flash_decode_stacked"


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return "conv" in (ctx.model.get("layer_pattern") or ())


def _span(ctx):
    return ctx.trace.get("span") if ctx.trace else None


def block_mean(ctx, field: int, traced: bool):
    """A field of ``tail_blocks`` (2: slots decoding, 3: live rows) the
    decode blocks held at dispatch, averaged by duration over the traced
    seconds' blocks or over the window's."""
    if not is_family(ctx):
        return None
    blocks = (tail_blocks(ctx, _span(ctx)) if traced else None) \
        or tail_blocks(ctx)
    total = sum(b[0] for b in blocks)
    return sum(b[field] * b[0] for b in blocks) / total if total > 0 \
        else None


def kernel_ms(ctx, name: str = DECODE_KERNEL):
    """Device time of the kernel ``name`` (all its layers) in one decode
    step, from the traced seconds."""
    if not is_family(ctx):
        return None
    steps = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: name in n)
    return s / steps * 1e3 if steps and s > 0 else None


def conv_seconds(ctx) -> float:
    """Device seconds of the decode step's operations that only a conv
    operator has: those whose output is [slots, (1,) 3 dim] (the
    in-projection W_in, three quarters of the operator's weights, with
    whatever XLA fused onto it) or a tail's [.., slots, conv_kernel - 1,
    dim] (the taps' shift and the select that puts the new tails in).
    The reduced trace keys operations by name and shape and carries no
    scope, so the out-projection and what is fused into it, whose output
    is [slots, dim] like a dozen other products of the step, are NOT
    told apart and not counted (PERF.md, Open questions: what reading
    the ``conv/*`` scopes would take)."""
    m = ctx.model
    shape = re.compile(r"\[%d,(1,)?%d\]|\[(\d+,)?%d,%d,%d\]" % (
        ctx.slots, 3 * m["dim"], ctx.slots, m["conv_kernel"] - 1, m["dim"]))
    return op_seconds(ctx, lambda n: bool(shape.search(n)))
