"""Tokens an expert gets in a pass of the block-diffusion family,
averaged over the layers, the experts and the window's passes: the
program's count of (token, expert) assignments over passes x layers x
experts. Every expert is held, so this is the deployment's own figure at
these slots: 96 slots x 4 positions x 8 / 128 = 24 where every slot is
active."""
from benchmarks.metrics._sdar import is_family, moe_blocks


def read(ctx):
    if not is_family(ctx):
        return None
    blocks = moe_blocks(ctx)
    passes = sum(b[1] for b in blocks)
    cells = ctx.model["n_layers"] * ctx.model["n_experts"]
    if not passes or not cells:
        return None
    return sum(b[3] for b in blocks) / passes / cells
