"""Index keys the traffic really holds, in GB (256 B a row a full layer):
the decode events' live positions, averaged over the window's blocks by
duration."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import is_family, rows_mean


def read(ctx):
    rows = rows_mean(ctx, 2, traced=False) if is_family(ctx) else None
    return None if rows is None \
        else rows * rf.index_bytes_per_token(ctx.model) / 1e9
