"""The routed experts' share of their roofline in the sparse-latent family:
the larger of (experts touched a step x 23.6 MB) / bandwidth and
(assignments a step x 6 x dim x width) / matrix peak, over their measured
time a step. The counts are the program's own (the decode events'
appended fields)."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import (expert_seconds, is_family,
                                             per_step_mean, traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), expert_seconds(ctx)
    assigned, touched = per_step_mean(ctx, 3), per_step_mean(ctx, 4)
    if not steps or s <= 0 or touched is None or ctx.peaks is None:
        return None
    least = rf.least_seconds(
        touched * rf.expert_bytes(ctx.model),
        assigned * rf.expert_flops_per_assignment(ctx.model), ctx.peaks)
    return 100.0 * least / (s / steps)
