"""K and V rows of the full layers the traffic really holds, in GB: the
decode events' live positions x 4 KiB a row x the full layers, averaged over
the window's blocks by duration. Beside kv.window_live_gb: under 2,048
positions the rings hold more, at long contexts these rows would."""
from benchmarks import roofline_laguna as rf
from benchmarks.metrics._laguna import is_family, rows_mean


def read(ctx):
    rows = rows_mean(ctx, 2, traced=False) if is_family(ctx) else None
    return None if rows is None \
        else rows * rf.kv_bytes_per_token(ctx.model) / 1e9
