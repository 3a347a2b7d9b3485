"""The step's write's share of its roofline in the looped family: the
tiles around each decoding slot's cursor in every table (4,096 B of rows
and 512 B of scales a KV head, K and V), read and written, over the
chip's bandwidth, over the write's measured time a step."""
from benchmarks import roofline_ouro as rf
from benchmarks.metrics._ouro import APPEND_KERNEL, block_mean, kernel_ms


def read(ctx):
    ms, slots = kernel_ms(ctx, APPEND_KERNEL), block_mean(ctx, 2, traced=True)
    if ms is None or slots is None or ctx.peaks is None:
        return None
    return 100.0 * rf.append_bytes(ctx.model, slots) \
        / ctx.peaks["hbm_bytes_per_s"] / (ms / 1e3)
