"""Ring rows the traffic really holds, in GB: the rows of a window layer's
rings the decoding slots held at each decode block (each cursor cut to the
ring: the program's count) x 2,304 B a stored row x the window layers,
averaged over the window's blocks by duration. Never more than slots x the
bytes a slot's rings take."""
from benchmarks import roofline_dots3_note as rf
from benchmarks.metrics._dots3_note import is_family, rows_mean


def read(ctx):
    rows = rows_mean(ctx, 3, traced=False) if is_family(ctx) else None
    return None if rows is None \
        else rows * rf.ring_bytes_per_row(ctx.model) / 1e9
