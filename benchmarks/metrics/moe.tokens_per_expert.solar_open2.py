"""Tokens a held expert gets in a decode step of the hybrid family,
averaged over layers, experts and the window's steps: the program's count
of (token, held expert) assignments over steps x layers x held experts.
The deployment this chip stands for would see 8 times as many (the
configuration's ``deployment``)."""
from benchmarks.metrics._solar_open2 import is_family, moe_blocks


def read(ctx):
    if not is_family(ctx):
        return None
    blocks = moe_blocks(ctx)
    steps = sum(b[1] for b in blocks)
    m = ctx.model
    cells = m["n_layers"] * (m["n_experts_held"] or m["n_experts"])
    if not steps or not cells:
        return None
    return sum(b[3] for b in blocks) / steps / cells
