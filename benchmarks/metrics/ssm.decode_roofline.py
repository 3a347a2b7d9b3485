"""The state-space decode kernel's share of its roofline: the active
(layer, slot) states a step (the program's count) x 2 x 4.19 MB, each
read once and written once, over the chip's bandwidth, over the kernel's
measured time a step. Bound by memory: a state value is touched by five
vector operations."""
from benchmarks import roofline_nemotron_h as rf
from benchmarks.metrics._nemotron_h import (DECODE_KERNEL, is_family,
                                             kernel_seconds, states_per_step,
                                             traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), kernel_seconds(ctx, DECODE_KERNEL)
    states = states_per_step(ctx)
    if not steps or s <= 0 or states is None or ctx.peaks is None:
        return None
    least = rf.decode_kernel_bytes(ctx.model, states) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (s / steps)
