"""Device time of the decode kernel over the window layers' rings
(ops/flash_decode.py's ``flash_decode_ring``, all six layers) in one decode
step, from the traced seconds."""
from benchmarks.metrics._laguna import RING_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, RING_KERNEL)
