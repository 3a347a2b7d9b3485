"""How much of the reserved slot pool one decode step's attention fetches,
as the generator counts it: the positions each decode block's kernel reads
for its active slots (each cursor rounded up to the kernel's block; every
reserved position where decode attention is on the reference path), which
the timeline's decode events carry beside the live positions, over slots x
positions reserved a slot, averaged over the window's blocks by their
duration. Beside kv.pool_fill_pct it says how far the step's KV stream is
from what is live. A program whose decode events have no such field reads
nothing: it attends over all that is reserved, which is 100."""
from benchmarks.metrics._lib import events


def read(ctx):
    blocks = [e for e in events(ctx, "decode")
              if len(e) > 7 and e[7] is not None]
    total = sum(e[2] for e in blocks)
    reserved = ctx.slots * (ctx.engine_stats or {}).get("max_seq", 0)
    if total <= 0 or not reserved:
        return None
    return 100.0 * sum(e[7] * e[2] for e in blocks) / total / reserved
