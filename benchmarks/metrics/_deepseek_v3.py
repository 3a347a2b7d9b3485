"""What the latent-attention family's readers share: the decode blocks'
expert counts from the program's timeline, the traced steps, device
seconds of named operations."""
import re

from benchmarks.metrics._lib import events, module_time

# the name the device trace gives ops/mla.py's kernel (its jitted function)
ATTN_KERNEL = ("decode_attention_stacked",)


def moe_blocks(ctx, span=None):
    """Decode events that carry the expert layer's count: (duration,
    steps, live rows, assignments, (step, layer, expert) cells touched)."""
    return [(e[2], e[5], e[6], e[8], e[9]) for e in events(ctx, "decode", span)
            if len(e) > 9 and e[8] is not None]


def traced_steps(ctx) -> int:
    count, _ = module_time(ctx, "_step_fn")
    return count * ctx.decode_block


def op_seconds(ctx, match) -> float:
    """Seconds of the first device's operations ``match(name)`` accepts."""
    if not ctx.trace or "ops" not in ctx.trace:
        return 0.0
    return sum(s for name, s in ctx.trace["ops"].items() if match(name))


def per_step_mean(ctx, field: int):
    """A decode event field (per block) as a mean a step over the traced
    seconds' blocks, weighted by steps."""
    span = ctx.trace.get("span") if ctx.trace else None
    blocks = moe_blocks(ctx, span) or moe_blocks(ctx)
    steps = sum(b[1] for b in blocks)
    return sum(b[field] for b in blocks) / steps if steps else None


def live_rows(ctx):
    """Cached rows the traced steps' attention had to read, a step, one
    layer: the decode events' live positions."""
    span = ctx.trace.get("span") if ctx.trace else None
    blocks = [e for e in events(ctx, "decode", span)
              if len(e) > 6 and e[6] is not None]
    total = sum(e[2] for e in blocks)
    return sum(e[6] * e[2] for e in blocks) / total if total > 0 else None


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return bool(ctx.model.get("kv_lora_rank"))


def expert_seconds(ctx) -> float:
    """Device seconds of the routed experts' dispatch and matmuls: the
    operations whose output is one dispatch block of rows tall (the gate
    and up matmuls) or the whole padded dispatch buffer tall (its gather,
    the down matmul written into it), which nothing else in the step is.
    The two heights are the program's own word for its decode step
    (``stats()["moe_decode_dispatch"]``), not reckoned here: a program
    that dispatches otherwise says other heights, or says none and the
    readers read nothing. The reduced trace keys operations by name and
    shape over ALL compiled programs, so a prefill of 128 tokens or
    fewer, which dispatches in blocks of the same height, is counted in
    (PERF.md, Open questions: what telling them apart would take)."""
    said = (ctx.engine_stats or {}).get("moe_decode_dispatch")
    if not said:
        return 0.0
    m = ctx.model
    shape = re.compile(r"\[(%d|%d),(%d|%d)\]" % (
        said["block_rows"], said["buffer_rows"], m["moe_ffn_dim"], m["dim"]))
    return op_seconds(ctx, lambda n: bool(shape.search(n)))
