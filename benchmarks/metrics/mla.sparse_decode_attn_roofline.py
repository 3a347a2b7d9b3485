"""The selecting layers' decode path against what it must fetch: the rows
kept x 1,280 B (a stored row) for the attention kernel and the live rows x
256 B (an index key) for the score kernel, each over the chip's bandwidth,
or their operations over the matrix peak if larger, summed, over the two
kernels' measured time a step. The attention kernel fetches whole blocks
of 256 rows up to the cursor, kept or not, so the share cannot pass 100 and
falls as a cell leaves rows out."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import (KEPT_KERNEL, SCORE_KERNEL,
                                             kept_mean, kernel_ms, rows_mean)


def read(ctx):
    attn, score = kernel_ms(ctx, KEPT_KERNEL), kernel_ms(ctx, SCORE_KERNEL)
    kept, live = kept_mean(ctx, True), rows_mean(ctx, 2, True)
    if None in (attn, score, kept, live) or ctx.peaks is None:
        return None
    m, layers = ctx.model, rf.kinds(ctx.model)["full"]
    least = rf.least_seconds(
        kept * rf.row_bytes(m, "full") * layers,
        kept * rf.attn_flops_per_row(m, "full") * layers, ctx.peaks) \
        + rf.least_seconds(live * rf.key_bytes(m) * layers,
                           live * rf.index_flops_per_row(m) * layers,
                           ctx.peaks)
    return 100.0 * least / ((attn + score) / 1e3)
