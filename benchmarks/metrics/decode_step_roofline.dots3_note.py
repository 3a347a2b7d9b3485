"""The whole decode step's share of its roofline, for the sparse-latent
family: the weights a step touches (attention of both kinds with the
indexer, the dense layer, shared experts, routers, head: all; routed
experts: those that got a token, by the program's count), an index key a
live row, a latent row a row kept and the live rows of the rings (the
program's counts, rows as HBM stores them), over the chip's peak bandwidth,
over the measured step."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import (is_family, kept_mean,
                                             per_step_mean, rows_mean)
from benchmarks.metrics._lib import decode_step_s


def read(ctx):
    if not is_family(ctx):
        return None
    step, touched = decode_step_s(ctx), per_step_mean(ctx, 4)
    live, ring = (rows_mean(ctx, f, traced=True) for f in (2, 3))
    kept = kept_mean(ctx, traced=True)
    if None in (step, touched, live, ring, kept) or ctx.peaks is None:
        return None
    return 100.0 * rf.step_bytes(ctx.model, touched, live, kept, ring) \
        / ctx.peaks["hbm_bytes_per_s"] / step
