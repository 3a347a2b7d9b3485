"""The whole decode step's share of its roofline, for the state-space
family: the weights a step touches (mamba and attn layers, routers,
latent pairs, shared experts, head: all; routed experts: those that got
a token, by the program's count), the active states read and written
once, and the live K and V rows of the attn layers, over the chip's
peak bandwidth, over the measured step. ``decode_step_roofline`` counts
Mistral's bytes and is not read in this family's cell."""
from benchmarks import roofline_nemotron_h as rf
from benchmarks.metrics._lib import decode_step_s
from benchmarks.metrics._nemotron_h import (is_family, live_rows,
                                             per_step_mean, states_per_step)


def read(ctx):
    if not is_family(ctx):
        return None
    step, rows = decode_step_s(ctx), live_rows(ctx)
    touched, states = per_step_mean(ctx, 4), states_per_step(ctx)
    if None in (step, rows, touched, states) or ctx.peaks is None:
        return None
    bytes_ = (rf.fixed_weight_bytes(ctx.model)
              + touched * rf.expert_bytes(ctx.model)
              + rf.decode_kernel_bytes(ctx.model, states)
              + rows * rf.kv_bytes_per_token(ctx.model))
    return 100.0 * bytes_ / ctx.peaks["hbm_bytes_per_s"] / step
