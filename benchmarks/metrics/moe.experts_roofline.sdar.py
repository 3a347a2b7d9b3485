"""The routed experts' share of their roofline in the block-diffusion
family, at 24 tokens an expert a pass: the larger of (experts touched a
pass x 4.7 MB) / bandwidth and (assignments a pass x 6 x dim x width) /
matrix peak, over their measured time a pass. The counts are the
program's own (the decode events' appended fields)."""
from benchmarks import roofline_sdar as rf
from benchmarks.metrics._sdar import (expert_seconds, is_family,
                                       per_step_mean, traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    passes, s = traced_steps(ctx), expert_seconds(ctx)
    assigned, touched = per_step_mean(ctx, 3), per_step_mean(ctx, 4)
    if not passes or s <= 0 or touched is None or ctx.peaks is None:
        return None
    least = rf.least_seconds(
        touched * rf.expert_bytes(ctx.model),
        assigned * rf.expert_flops_per_assignment(ctx.model), ctx.peaks)
    return 100.0 * least / (s / passes)
