"""The block-decode kernel's share of its roofline: live positions (the
program's count at dispatch) x 2 KiB a row x twelve layers over the
chip's bandwidth, or their operations at four query positions a slot
over the matrix peak if larger, over the kernel's measured time a
pass."""
from benchmarks import roofline_sdar as rf
from benchmarks.metrics._sdar import kernel_ms, mean_by_duration


def read(ctx):
    ms, rows = kernel_ms(ctx), mean_by_duration(ctx, 3, traced=True)
    if ms is None or rows is None or ctx.peaks is None:
        return None
    m = ctx.model
    least = rf.least_seconds(
        rows * rf.row_bytes(m) * m["n_layers"],
        rows * m["block_length"] * rf.attn_flops_per_row(m) * m["n_layers"],
        ctx.peaks)
    return 100.0 * least / (ms / 1e3)
