"""Device time of one decode step: the fused decode block's time in the
traced seconds over the steps it holds."""
from benchmarks.metrics._lib import decode_step_s


def read(ctx):
    s = decode_step_s(ctx)
    return None if s is None else s * 1e3
