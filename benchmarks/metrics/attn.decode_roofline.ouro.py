"""The decode kernel's share of its roofline in the looped family: the
positions it must fetch (each cursor rounded up to its block of 256; the
program's count at dispatch) x 811,008 B a position over all tables,
rows and scales, over the chip's bandwidth, over the kernel's measured
time a step."""
from benchmarks import roofline_ouro as rf
from benchmarks.metrics._ouro import DECODE_KERNEL, block_mean, kernel_ms


def read(ctx):
    ms, fetched = kernel_ms(ctx, DECODE_KERNEL), \
        block_mean(ctx, 4, traced=True)
    if ms is None or fetched is None or ctx.peaks is None:
        return None
    return 100.0 * fetched * rf.kv_bytes_per_token(ctx.model) \
        / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
