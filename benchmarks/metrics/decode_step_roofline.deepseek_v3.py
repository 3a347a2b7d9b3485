"""The whole decode step's share of its roofline, for the latent-attention
family: the weights a step touches (attention, dense and shared
feed-forward, router, head: all; routed experts: those that got a token,
by the program's count) plus the live latent rows, over the chip's peak
bandwidth, over the measured step. ``decode_step_roofline`` counts
Mistral's bytes and is not read in this family's cells."""
from benchmarks import roofline_deepseek_v3 as rf
from benchmarks.metrics._deepseek_v3 import (is_family, live_rows,
                                              per_step_mean)
from benchmarks.metrics._lib import decode_step_s


def read(ctx):
    if not is_family(ctx):
        return None
    step, rows = decode_step_s(ctx), live_rows(ctx)
    touched = per_step_mean(ctx, 4)
    if step is None or rows is None or touched is None or ctx.peaks is None:
        return None
    bytes_ = (rf.fixed_weight_bytes(ctx.model)
              + touched * rf.expert_bytes(ctx.model)
              + rows * rf.kv_bytes_per_token(ctx.model))
    return 100.0 * bytes_ / ctx.peaks["hbm_bytes_per_s"] / step
