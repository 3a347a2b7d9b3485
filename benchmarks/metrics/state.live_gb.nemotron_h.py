"""State-space state the traffic really holds, in GB: the slots decoding
in each decode block x the bytes a slot's state takes (the program's
``stats()["state_bytes_per_slot"]``: state and convolution tail of every
mamba layer, whatever the slot's length), averaged over the window's
blocks by duration. Beside hbm.in_use_gb, which counts every slot's."""
from benchmarks.metrics._nemotron_h import is_family, state_blocks


def read(ctx):
    per_slot = (ctx.engine_stats or {}).get("state_bytes_per_slot")
    blocks = state_blocks(ctx)
    total = sum(b[0] for b in blocks)
    if not is_family(ctx) or not per_slot or total <= 0:
        return None
    return sum(b[2] * b[0] for b in blocks) / total * per_slot / 1e9
