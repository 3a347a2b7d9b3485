"""Seconds in ``EnginePrograms.allocate`` during set-up: the serving cache,
the prefix pool and a scratch row, each a phase of its own with its tag and
bytes in the account."""
from benchmarks.metrics._startup import phase_seconds


def read(ctx):
    return phase_seconds(ctx, "allocate")
