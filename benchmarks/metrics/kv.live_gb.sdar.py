"""K and V rows the traffic really holds, in GB: the decode events' live
positions x 24 KiB a token (twelve layers of four KV heads of 128,
bfloat16), averaged over the window's dispatches by duration. Beside
hbm.in_use_gb it says how much of the reserved cache is read a pass."""
from benchmarks import roofline_sdar as rf
from benchmarks.metrics._sdar import mean_by_duration


def read(ctx):
    rows = mean_by_duration(ctx, 3, traced=False)
    return None if rows is None \
        else rows * rf.kv_bytes_per_token(ctx.model) / 1e9
