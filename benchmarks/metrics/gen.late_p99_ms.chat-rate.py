"""How late the load generator sent: send time - due time, 99th percentile.
A starved generator is not a fast server."""
from benchmarks.metrics._lib import pct


def read(ctx):
    return pct([(s["sent"] - s["due"]) * 1e3 for s in ctx.window
                if "due" in s], 99)
