"""Latent cache rows the traffic really holds, in GB of values (1,152 B a
row a layer; HBM stores 1,280), as the generator counts them: the decode
events' live positions, averaged over the window's blocks by duration.
Beside hbm.in_use_gb, which counts the whole reserved pool."""
from benchmarks import roofline_deepseek_v3 as rf
from benchmarks.metrics._deepseek_v3 import is_family
from benchmarks.metrics._lib import events


def read(ctx):
    blocks = [e for e in events(ctx, "decode")
              if len(e) > 6 and e[6] is not None]
    total = sum(e[2] for e in blocks)
    if total <= 0 or not is_family(ctx):
        return None
    rows = sum(e[6] * e[2] for e in blocks) / total
    return rows * rf.kv_bytes_per_token(ctx.model) / 1e9
