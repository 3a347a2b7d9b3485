"""Device time of the routed experts' dispatch and matmuls (all twelve
layers) in one pass of the block-diffusion family, from the traced
seconds: ``moe.experts_ms``'s arithmetic (``_deepseek_v3.expert_seconds``
says which operations those are) from this family's ``stats()`` and
``ctx.model``."""
from benchmarks.metrics._sdar import expert_seconds, is_family, traced_steps


def read(ctx):
    if not is_family(ctx):
        return None
    passes, s = traced_steps(ctx), expert_seconds(ctx)
    return s / passes * 1e3 if passes and s > 0 else None
