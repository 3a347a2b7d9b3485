"""Tokens a held expert gets in a decode step of the sparse-latent family,
averaged over the routed layers, the held experts and the window's steps:
the program's count of (token, held expert) assignments over steps x
routed layers x held experts. 4 here where the deployment's 8 replicas
would give 32 (the configuration's caveat 1)."""
from benchmarks.metrics._dots3_note import is_family, moe_blocks


def read(ctx):
    if not is_family(ctx):
        return None
    blocks = moe_blocks(ctx)
    steps = sum(b[1] for b in blocks)
    m = ctx.model
    cells = (m["n_layers"] - m["n_dense_layers"]) \
        * (m["n_experts_held"] or m["n_experts"])
    if not steps or not cells:
        return None
    return sum(b[3] for b in blocks) / steps / cells
