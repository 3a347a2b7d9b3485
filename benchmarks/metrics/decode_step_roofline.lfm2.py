"""The whole decode step's share of its roofline, for the conv family:
the weights a step touches (every operator, the dense layers, routers,
the tied head: all; routed experts: those that got a token, by the
program's count), the live K and V rows of the full layers and the tails
of the slots that decode, read and written (the program's counts), over
the chip's peak bandwidth, over the measured step. ``decode_step_roofline``
counts Mistral's bytes and is not read in this family's cell."""
from benchmarks import roofline_lfm2 as rf
from benchmarks.metrics._lfm2 import block_mean, is_family, per_step_mean
from benchmarks.metrics._lib import decode_step_s


def read(ctx):
    if not is_family(ctx):
        return None
    step, touched = decode_step_s(ctx), per_step_mean(ctx, 4)
    slots, rows = (block_mean(ctx, f, traced=True) for f in (2, 3))
    if None in (step, touched, slots, rows) or ctx.peaks is None:
        return None
    return 100.0 * rf.step_bytes(ctx.model, touched, rows, slots) \
        / ctx.peaks["hbm_bytes_per_s"] / step
