"""Device time of the routed experts' dispatch and matmuls (all ten moe
layers, two-matrix relu^2 experts in the latent) in one decode step of
the state-space family, from the traced seconds."""
from benchmarks.metrics._nemotron_h import (expert_seconds, is_family,
                                             traced_steps)


def read(ctx):
    if not is_family(ctx):
        return None
    steps, s = traced_steps(ctx), expert_seconds(ctx)
    return s / steps * 1e3 if steps and s > 0 else None
