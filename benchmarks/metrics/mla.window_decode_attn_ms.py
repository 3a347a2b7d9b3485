"""Device time of the absorbed decode attention over the window layers'
rings (ops/mla.py's ``decode_attention_ring``, all six layers) in one
decode step, from the traced seconds."""
from benchmarks.metrics._dots3_note import RING_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, RING_KERNEL)
