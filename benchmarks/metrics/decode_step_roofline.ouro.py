"""The whole decode step's share of its roofline, for the looped family:
the stack's projections ``loop_steps`` times and the head once, the
cached positions the decode kernel fetches (whole blocks; the program's
count at dispatch) in every one of ``loop_steps x n_layers`` tables with
their scales, and the write's tiles for the slots that decode, over the
chip's peak bandwidth, over the measured step. ``decode_step_roofline``
counts Mistral's bytes and is not read in this family's cell."""
from benchmarks import roofline_ouro as rf
from benchmarks.metrics._lib import decode_step_s
from benchmarks.metrics._ouro import block_mean, is_family


def read(ctx):
    if not is_family(ctx):
        return None
    step = decode_step_s(ctx)
    slots, fetched = (block_mean(ctx, f, traced=True) for f in (2, 4))
    if None in (step, slots, fetched) or ctx.peaks is None:
        return None
    return 100.0 * rf.step_bytes(ctx.model, fetched, slots) \
        / ctx.peaks["hbm_bytes_per_s"] / step
