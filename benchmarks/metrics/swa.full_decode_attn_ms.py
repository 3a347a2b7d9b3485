"""Device time of the decode kernel over the full layers' rows
(``flash_decode_stacked`` with a group of 6 query heads a KV head, both
layers) in one decode step of the window family, from the traced seconds."""
from benchmarks.metrics._laguna import FULL_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, FULL_KERNEL)
