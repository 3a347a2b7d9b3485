"""The ring kernel's share of its roofline: the rows of a window layer's
rings the slots held (the program's count, each cursor cut to the ring) x
2,304 B a stored row x six layers over the chip's bandwidth, or their
operations over the matrix peak if larger, over the kernel's measured time
a step. The kernel fetches whole blocks of 256 rows, so the share cannot
pass 100."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import RING_KERNEL, kernel_ms, rows_mean


def read(ctx):
    ms, rows = kernel_ms(ctx, RING_KERNEL), rows_mean(ctx, 3, True)
    if ms is None or rows is None or ctx.peaks is None:
        return None
    m, layers = ctx.model, rf.kinds(ctx.model)["window"]
    least = rf.least_seconds(
        rows * rf.row_bytes(m, "window") * layers,
        rows * rf.attn_flops_per_row(m, "window") * layers, ctx.peaks)
    return 100.0 * least / (ms / 1e3)
