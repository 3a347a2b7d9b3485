"""The 64-wide decode kernel's share of its roofline in the conv family:
live positions (the program's count at dispatch) x 2 KiB a row x five
layers over the chip's bandwidth, or their operations over the matrix
peak if larger, over the kernel's measured time a step."""
from benchmarks import roofline_lfm2 as rf
from benchmarks.metrics._lfm2 import block_mean, kernel_ms


def read(ctx):
    ms, rows = kernel_ms(ctx), block_mean(ctx, 3, traced=True)
    if ms is None or rows is None or ctx.peaks is None:
        return None
    layers = rf.kinds(ctx.model)["full"]
    least = rf.least_seconds(
        rows * rf.row_bytes(ctx.model) * layers,
        rows * rf.attn_flops_per_row(ctx.model) * layers, ctx.peaks)
    return 100.0 * least / (ms / 1e3)
