"""Tokens an expert gets in a decode step of the conv family, averaged
over the routed layers, the experts and the window's steps: the program's
count of (token, expert) assignments over steps x routed layers x experts.
Every expert is held, so this is the deployment's own figure at these slots."""
from benchmarks.metrics._lfm2 import is_family, moe_blocks


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
