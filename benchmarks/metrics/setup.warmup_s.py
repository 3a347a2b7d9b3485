"""Seconds of the first ``warmup`` phase: every program call of the
generator's warm-up (and the batchers', where the engine warms itself),
compiles or cache loads and the dummy runs together."""
from benchmarks.metrics._startup import first_warmup


def read(ctx):
    phase = first_warmup(ctx)
    return phase["seconds"] if phase else None
