"""Device time of the block-decode kernel (``flash_decode_block``: four
query positions a slot over its live rows, a KV head's tile of 32 query
rows against each fetched block, all twelve layers) in one pass, from
the traced seconds."""
from benchmarks.metrics._sdar import kernel_ms


def read(ctx):
    return kernel_ms(ctx)
