"""Slots decoding, as a share of all slots, averaged over the decode blocks
of the window by their duration."""
from benchmarks.metrics._lib import events


def read(ctx):
    blocks = events(ctx, "decode")
    total = sum(e[2] for e in blocks)
    if total <= 0:
        return None
    return 100.0 * sum(len(e[4]) * e[2] for e in blocks) / total / ctx.slots
