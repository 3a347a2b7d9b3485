"""Device time of the state-space decode kernel (ops/ssd.py's
``ssd_decode``, all mamba layers) in one decode step, from the traced
seconds."""
from benchmarks.metrics._nemotron_h import (DECODE_KERNEL, is_family,
                                             kernel_seconds, traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), kernel_seconds(ctx, DECODE_KERNEL)
    return s / steps * 1e3 if steps and s > 0 else None
