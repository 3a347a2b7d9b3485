"""KV cache that the traffic really holds in the middle of the traced
seconds, all chips together: live tokens x bytes a token (from shapes,
benchmarks/roofline.py). Beside hbm.in_use_gb, which counts the whole
reserved slot pool, it says how full the pool is."""
from benchmarks import roofline
from benchmarks.metrics._lib import live_tokens, trace_mid


def read(ctx):
    mid = trace_mid(ctx)
    if mid is None:
        return None
    return live_tokens(ctx, mid) * roofline.kv_bytes_per_token(ctx.model) / 1e9
