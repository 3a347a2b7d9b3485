"""Tokens a slot delivers a pass: the tokens the window's dispatches
delivered over the slot-passes they ran (the device's own counters, on
the decode events). 4/3 where a block of four takes two denoise passes
and one commit pass; under it where streams end inside a block or start
with positions given."""
from benchmarks.metrics._sdar import blocks


def read(ctx):
    bs = blocks(ctx, traced=False)
    ran = sum(b[6] for b in bs)
    return sum(b[7] for b in bs) / ran if ran else None
