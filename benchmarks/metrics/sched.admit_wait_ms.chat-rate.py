"""Queue entry to admission (the span tpu.admit-wait, read from the
timeline's admit events), median inside the window."""
from benchmarks.metrics._lib import events, pct


def read(ctx):
    waits = [e[6][1] * 1e3 for e in events(ctx, "admit")]
    return pct(waits, 50)
