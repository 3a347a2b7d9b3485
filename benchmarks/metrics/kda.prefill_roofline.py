"""The prefill kernel's share of its roofline: over the prompt tokens the
traced seconds' prefill programs ran (buckets and chunks as dispatched,
padding included), the larger of its bytes (q, k, beta k, alpha, v in and
o out, float32) over the bandwidth and its operations over the matrix
peak, all linear layers, over ``kda_prefill``'s traced time. The kernel
runs the recurrence a token at a time on the vector unit, so this reads
low; the chunkwise form is what would raise it (PERF.md section 7)."""
from benchmarks import roofline_solar_open2 as rf
from benchmarks.metrics._solar_open2 import (PREFILL_KERNEL, is_family,
                                              kernel_seconds,
                                              prefilled_tokens)


def read(ctx):
    if not is_family(ctx):
        return None
    s, tokens = kernel_seconds(ctx, PREFILL_KERNEL), prefilled_tokens(ctx)
    if s <= 0 or not tokens or ctx.peaks is None:
        return None
    layers = rf.kinds(ctx.model)[1]
    least = rf.least_seconds(
        tokens * layers * rf.prefill_kernel_bytes_per_token(ctx.model),
        tokens * layers * rf.prefill_kernel_flops_per_token(ctx.model),
        ctx.peaks)
    return 100.0 * least / s
