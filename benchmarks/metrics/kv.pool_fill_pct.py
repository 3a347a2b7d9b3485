"""How full the reserved slot pool is, as the generator counts it: the KV
positions each decode block's slots held at dispatch (the timeline's decode
events) over slots x positions reserved a slot, averaged over the window's
blocks by their duration."""
from benchmarks.metrics._lib import events


def read(ctx):
    blocks = [e for e in events(ctx, "decode")
              if len(e) > 6 and e[6] is not None]
    total = sum(e[2] for e in blocks)
    reserved = ctx.slots * (ctx.engine_stats or {}).get("max_seq", 0)
    if total <= 0 or not reserved:
        return None
    return 100.0 * sum(e[6] * e[2] for e in blocks) / total / reserved
