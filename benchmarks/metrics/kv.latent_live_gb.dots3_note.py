"""Latent rows of the full layers the traffic really holds, in GB as HBM
stores them (1,280 B a row a layer): the decode events' live positions,
averaged over the window's blocks by duration. Beside hbm.in_use_gb, which
counts the whole reserved pool."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import is_family, rows_mean


def read(ctx):
    rows = rows_mean(ctx, 2, traced=False) if is_family(ctx) else None
    return None if rows is None \
        else rows * rf.latent_bytes_per_token(ctx.model) / 1e9
