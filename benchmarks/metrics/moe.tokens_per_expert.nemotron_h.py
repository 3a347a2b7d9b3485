"""Tokens a held expert gets in a decode step of the state-space family,
averaged over the moe layers, experts and the window's steps: the
program's count of (token, held expert) assignments over steps x moe
layers x held experts. The deployment this chip stands for would see 4
times as many (the configuration's ``deployment``)."""
from benchmarks import roofline_nemotron_h as rf
from benchmarks.metrics._nemotron_h import is_family, moe_blocks


def read(ctx):
    if not is_family(ctx):
        return None
    blocks = moe_blocks(ctx)
    steps = sum(b[1] for b in blocks)
    m = ctx.model
    cells = rf.kinds(m)[1] * (m["n_experts_held"] or m["n_experts"])
    if not steps or not cells:
        return None
    return sum(b[3] for b in blocks) / steps / cells
