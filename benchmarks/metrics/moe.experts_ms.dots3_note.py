"""Device time of the routed experts' matmuls (all eight routed layers) in
one decode step of the sparse-latent family, from the traced seconds:
``moe.experts_ms``'s arithmetic (``_deepseek_v3.expert_seconds`` says which
operations those are) from this family's ``stats()`` and ``ctx.model``."""
from benchmarks.metrics._dots3_note import (expert_seconds, is_family,
                                             traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), expert_seconds(ctx)
    return s / steps * 1e3 if steps and s > 0 else None
