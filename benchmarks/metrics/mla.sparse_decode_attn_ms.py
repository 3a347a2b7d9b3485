"""Device time of the absorbed decode attention over the rows the selection
kept (ops/mla.py's ``decode_attention_kept``, all three full layers) in
one decode step, from the traced seconds."""
from benchmarks.metrics._dots3_note import KEPT_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, KEPT_KERNEL)
