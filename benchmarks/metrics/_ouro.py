"""What the looped family's readers share: the decode blocks' counts from
the program's timeline (slots decoding, live positions, positions the
kernel fetched), the kernels' names in a device trace, the shapes that
only the decode block's projections have."""
import re

from benchmarks.metrics._deepseek_v3 import op_seconds, traced_steps
from benchmarks.metrics._lib import events

# the names the device trace gives ops/flash_decode.py's kernels (their
# jitted functions)
DECODE_KERNEL = "flash_decode_stacked"
APPEND_KERNEL = "append_rows_stacked"


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return (ctx.model.get("loop_steps") or 1) > 1 \
        or bool(ctx.model.get("sandwich_norm"))


def _span(ctx):
    return ctx.trace.get("span") if ctx.trace else None


def decode_blocks(ctx, span=None):
    """Decode events: (duration, steps, slots decoding, live positions,
    positions the kernel fetches a step)."""
    return [(e[2], e[5], len(e[4] or ()), e[6], e[7])
            for e in events(ctx, "decode", span)
            if len(e) > 7 and e[6] is not None and e[7] is not None]


def block_mean(ctx, field: int, traced: bool):
    """A field of ``decode_blocks`` (2: slots decoding, 3: live
    positions, 4: positions fetched) the decode blocks held at dispatch,
    averaged by duration over the traced seconds' blocks or over the
    window's."""
    if not is_family(ctx):
        return None
    blocks = (decode_blocks(ctx, _span(ctx)) if traced else None) \
        or decode_blocks(ctx)
    total = sum(b[0] for b in blocks)
    return sum(b[field] * b[0] for b in blocks) / total if total > 0 \
        else None


def kernel_ms(ctx, name: str):
    """Device time of the kernel ``name`` (all its tables) in one decode
    step, from the traced seconds."""
    if not is_family(ctx):
        return None
    steps = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: name in n)
    return s / steps * 1e3 if steps and s > 0 else None


def projection_seconds(ctx) -> float:
    """Device seconds of the decode block's seven projections a layer a
    pass, with what XLA fused onto them. On the chip a product is a
    fusion named after whatever it was fused with (``fusion.N``,
    ``multiply_convert_fusion.N``: SiLU(gate) x up; ``multiply_reduce_
    fusion.N``: a product with the next norm's sum of squares, whose
    FIRST output is that statistic, ``f32[slots]``), so the reduced
    trace, which keys an operation by its name and its first output's
    shape, cannot tell a product from a norm by name. It can by shape:
    counted are the fusions whose first output is one value a slot or one
    row a slot at a width only a layer's projection has (the hidden
    size, the heads' values, the feed-forward's): the layer loop's
    products AND its norms, rotations' inputs and residual adds, which
    move kilobytes where a product streams megabytes. The share read off
    this time is therefore a little under the products' own, never over.
    Not counted: the two kernels, the head ([slots, vocabulary]), a
    prefill's operations ([1, tokens, width] or [tokens, width]: no
    prompt bucket of the cell is as tall as the slots are many)."""
    m = ctx.model
    hd = m["attn_head_dim"] or m["dim"] // m["n_heads"]
    widths = {m["dim"], m["ffn_dim"], m["n_heads"] * hd,
              m["n_kv_heads"] * hd}
    shape = re.compile(r" (bf16|f32)\[%d(,1)?(,(%s))?\]$" % (
        ctx.slots, "|".join(str(w) for w in sorted(widths))))
    return op_seconds(ctx, lambda n: "fusion" in n.split(" ")[0]
                      and bool(shape.search(n)))
