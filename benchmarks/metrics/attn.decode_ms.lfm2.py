"""Device time of the decode kernel over the full layers' rows
(``flash_decode_stacked`` on rows of two 64-wide KV heads, a pair's group
of eight query heads, all five layers) in one decode step of the conv
family, from the traced seconds."""
from benchmarks.metrics._lfm2 import kernel_ms


def read(ctx):
    return kernel_ms(ctx)
