"""Device time in one decode step of the operations that only the gated
short convolution has (all fifteen conv layers): its in-projection and
what moves a tail, from the traced seconds (``_lfm2.conv_seconds`` says
which operations those are, and which of the operator's it cannot tell
from the rest of the step)."""
from benchmarks.metrics._lfm2 import conv_seconds, is_family, traced_steps


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), conv_seconds(ctx)
    return s / steps * 1e3 if steps and s > 0 else None
