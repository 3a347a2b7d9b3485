"""The chunk kernel's share of its roofline: over the prompt positions
the traced seconds' prefill programs ran (buckets and chunks as
dispatched, padding included), the larger of its bytes (Delta x in, y
out, B, C, log a: float32) over the bandwidth and its matrix operations
(the chunk form's, chunks of 128) over the matrix peak, all mamba
layers, over ``ssd_prefill``'s traced time. The kernel multiplies in
float32 (several passes of the bfloat16 unit) and reads three arrays of
the inner width where one would do, so this reads low."""
from benchmarks import roofline_nemotron_h as rf
from benchmarks.metrics._nemotron_h import (PREFILL_KERNEL, is_family,
                                             kernel_seconds,
                                             prefilled_tokens)


def read(ctx):
    if not is_family(ctx):
        return None
    s, tokens = kernel_seconds(ctx, PREFILL_KERNEL), prefilled_tokens(ctx)
    if s <= 0 or not tokens or ctx.peaks is None:
        return None
    layers = rf.kinds(ctx.model)[0]
    least = rf.least_seconds(
        tokens * layers * rf.prefill_kernel_bytes_per_token(ctx.model),
        tokens * layers * rf.prefill_kernel_flops_per_token(ctx.model),
        ctx.peaks)
    return 100.0 * least / s
