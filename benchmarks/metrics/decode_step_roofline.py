"""The decode step's share of its HBM roofline: the bytes a step must
stream (weights once + the KV of the tokens live in the traced seconds, from
shapes, benchmarks/roofline.py; a chip's share on a mesh) over the chip's
peak bandwidth, over the measured step."""
from benchmarks import roofline
from benchmarks.metrics._lib import decode_step_s, live_tokens, trace_mid


def read(ctx):
    step = decode_step_s(ctx)
    if step is None or ctx.peaks is None:
        return None
    return roofline.decode_step_roofline_pct(
        ctx.model, live_tokens(ctx, trace_mid(ctx)), step, ctx.peaks,
        ctx.chips)
