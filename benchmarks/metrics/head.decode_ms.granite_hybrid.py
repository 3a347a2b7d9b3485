"""Device time, in one decode step, of every operation that WRITES
[slots, vocab]: the tied head's product (the final norm's stream against
the bfloat16 embedding table, 411 MB) with what XLA fuses onto that
output (the division by logits_scaling; on the chip one
``convolution_multiply_fusion f32[96,100352]``). The reduced trace keeps
no scope, so anything else of that shape would be counted too; the
sampler's passes write [slots] and are not."""
from benchmarks.metrics._granite_hybrid import shaped_ms


def read(ctx):
    return shaped_ms(ctx, ctx.model.get("vocab_size") or 0)
