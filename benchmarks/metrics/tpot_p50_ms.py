"""Per request (last - first token) / (tokens - 1), median over the
requests due in the window."""
from benchmarks.metrics._lib import pct, tpot_ms


def read(ctx):
    return pct(tpot_ms(ctx), 50)
