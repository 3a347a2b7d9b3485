"""The whole decode step's share of its roofline, for the window family:
the weights a step touches (attention of both kinds, the dense layer, shared
experts, routers, head: all; routed experts: those that got a token, by the
program's count), the live K and V rows of the full layers and the live
rows of the window layers' rings (the program's counts), over the chip's
peak bandwidth, over the measured step. ``decode_step_roofline`` counts
Mistral's bytes and is not read in this family's cell."""
from benchmarks import roofline_laguna as rf
from benchmarks.metrics._laguna import is_family, per_step_mean, rows_mean
from benchmarks.metrics._lib import decode_step_s


def read(ctx):
    if not is_family(ctx):
        return None
    step, touched = decode_step_s(ctx), per_step_mean(ctx, 4)
    full, ring = (rows_mean(ctx, f, traced=True) for f in (2, 3))
    if None in (step, touched, full, ring) or ctx.peaks is None:
        return None
    return 100.0 * rf.step_bytes(ctx.model, touched, full, ring) \
        / ctx.peaks["hbm_bytes_per_s"] / step
