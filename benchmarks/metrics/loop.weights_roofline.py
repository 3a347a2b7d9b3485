"""The projections' share of their roofline in the looped family: the
stack's int8 weights and scales ``loop_steps`` times over the chip's
bandwidth, over the projections' measured time a step."""
from benchmarks import roofline_ouro as rf
from benchmarks.metrics._ouro import (is_family, projection_seconds,
                                      traced_steps)


def read(ctx):
    if not is_family(ctx) or ctx.peaks is None:
        return None
    steps, s = traced_steps(ctx), projection_seconds(ctx)
    if not steps or s <= 0:
        return None
    return 100.0 * rf.loop_weight_bytes(ctx.model) \
        / ctx.peaks["hbm_bytes_per_s"] / (s / steps)
