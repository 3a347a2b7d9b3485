"""Device time of the absorbed decode attention (ops/mla.py's kernel, all
layers) in one decode step, from the traced seconds."""
from benchmarks.metrics._deepseek_v3 import (ATTN_KERNEL, is_family,
                                              op_seconds, traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: any(k in n for k in ATTN_KERNEL))
    return s / steps * 1e3 if steps and s > 0 else None
