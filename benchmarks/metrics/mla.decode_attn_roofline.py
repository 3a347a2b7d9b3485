"""The absorbed decode attention's share of its roofline: the larger of
(live rows x 1,152 B x layers) / bandwidth and (live rows x 2 x heads x
(576 + 512) x layers) / matrix peak, over its measured time a step. Every
head shares a row, so at 64 heads it sits near the chip's ridge."""
from benchmarks import roofline_deepseek_v3 as rf
from benchmarks.metrics._deepseek_v3 import (ATTN_KERNEL, is_family,
                                              live_rows, op_seconds,
                                              traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, rows = traced_steps(ctx), live_rows(ctx)
    s = op_seconds(ctx, lambda n: any(k in n for k in ATTN_KERNEL))
    if not steps or s <= 0 or rows is None or ctx.peaks is None:
        return None
    layers = ctx.model["n_layers"]
    least = rf.least_seconds(rows * rf.row_bytes(ctx.model) * layers,
                             rows * rf.attn_flops_per_row(ctx.model) * layers,
                             ctx.peaks)
    return 100.0 * least / (s / steps)
