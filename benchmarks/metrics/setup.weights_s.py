"""Seconds of the ``weights`` phase: the parameters made leaf by leaf from
the seed (or loaded, quantized and placed), until the last leaf is on the
device."""
from benchmarks.metrics._startup import phase_seconds


def read(ctx):
    return phase_seconds(ctx, "weights")
