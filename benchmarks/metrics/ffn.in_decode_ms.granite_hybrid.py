"""Device time of the 40 gated feed-forwards' IN-product and gate in one
decode step: the operations whose first output is [slots, 2 ffn_dim]
(``ffn/in``, two thirds of the feed-forward's 2.0 GB) or [slots,
ffn_dim] (the gate, and the out-product where XLA fuses the gate into
it). NOT the whole feed-forward: an out-product that stands alone writes
[slots, dim] like the mixers' projections, and the reduced trace keeps
no scope to tell ``ffn/out`` by (PERF.md, Open questions 18(a))."""
from benchmarks.metrics._granite_hybrid import shaped_ms


def read(ctx):
    f = ctx.model.get("ffn_dim") or 0
    return shaped_ms(ctx, 2 * f, f)
