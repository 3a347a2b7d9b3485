"""K and V rows of the full layers the traffic really holds, in GB: the
decode events' live positions x 10 KiB a token (five full layers of
twenty), averaged over the window's blocks by duration. Beside
hbm.in_use_gb it says how little of this family's chip the cache is."""
from benchmarks import roofline_lfm2 as rf
from benchmarks.metrics._lfm2 import block_mean


def read(ctx):
    rows = block_mean(ctx, 3, traced=False)
    return None if rows is None \
        else rows * rf.kv_bytes_per_token(ctx.model) / 1e9
