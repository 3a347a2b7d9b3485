"""Device time in one decode step of the layer loop's products: the
seven projections of every layer of every pass with what XLA fused onto
them (``_ouro.projection_seconds`` says which operations those are, and
that the loop's norms ride in the count), from the traced seconds: what
the loop costs a step in weight stream."""
from benchmarks.metrics._ouro import (is_family, projection_seconds,
                                      traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), projection_seconds(ctx)
    return s / steps * 1e3 if steps and s > 0 else None
