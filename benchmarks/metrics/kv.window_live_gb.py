"""Ring rows the traffic really holds, in GB: the rows of a window layer's
rings the decoding slots held at each decode block (each cursor cut to the
window: the program's count) x 4 KiB a row x the window layers, averaged
over the window's blocks by duration. Never more than slots x the bytes a
slot's rings take. Beside hbm.in_use_gb, which counts every slot's."""
from benchmarks import roofline_laguna as rf
from benchmarks.metrics._laguna import is_family, rows_mean


def read(ctx):
    rows = rows_mean(ctx, 3, traced=False) if is_family(ctx) else None
    return None if rows is None \
        else rows * rf.ring_bytes_per_row(ctx.model) / 1e9
