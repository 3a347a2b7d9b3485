"""Convolution tails the traffic really holds, in GB: the slots decoding
in each decode block x the bytes a slot's tails take (the program's
``stats()["state_bytes_per_slot"]``: the last two inputs of every conv
layer, whatever the slot's length), averaged over the window's blocks by
duration."""
from benchmarks.metrics._lfm2 import block_mean


def read(ctx):
    per_slot = (ctx.engine_stats or {}).get("state_bytes_per_slot")
    slots = block_mean(ctx, 2, traced=False)
    return None if not per_slot or slots is None \
        else slots * per_slot / 1e9
