"""Device time of the routed experts' matmuls (all routed layers) in one
decode step, from the traced seconds (``_deepseek_v3.expert_seconds`` says
which operations those are)."""
from benchmarks.metrics._deepseek_v3 import (expert_seconds, is_family,
                                              traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), expert_seconds(ctx)
    return s / steps * 1e3 if steps and s > 0 else None
