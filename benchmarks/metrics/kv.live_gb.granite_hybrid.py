"""K and V rows of the attn layers the traffic really holds, in GB: the
decode events' live positions x 8 KiB a token (four attn layers of 8 KV
heads of 64, bfloat16), averaged over the window's blocks by duration.
Beside state.live_gb.nemotron_h it says how small a share of this
family's cache the rows are under 2,048 positions."""
from benchmarks import roofline_granite_hybrid as rf
from benchmarks.metrics._granite_hybrid import is_family, state_blocks


def read(ctx):
    blocks = state_blocks(ctx)
    total = sum(b[0] for b in blocks)
    if not is_family(ctx) or total <= 0:
        return None
    return sum(b[3] * b[0] for b in blocks) / total \
        * rf.kv_bytes_per_token(ctx.model) / 1e9
