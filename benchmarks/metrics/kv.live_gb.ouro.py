"""K and V rows the traffic really holds, in GB: the decode events' live
positions x 811,008 B a position (192 tables of 16 KV heads, int8 with
their scales), averaged over the window's blocks by duration.
``kv.live_gb`` reckons a token from ``n_layers`` and would read a
quarter."""
from benchmarks import roofline_ouro as rf
from benchmarks.metrics._ouro import block_mean


def read(ctx):
    rows = block_mean(ctx, 3, traced=False)
    return None if rows is None \
        else rows * rf.kv_bytes_per_token(ctx.model) / 1e9
