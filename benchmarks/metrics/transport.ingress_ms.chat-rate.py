"""From the request's HEADERS frame to the engine's submit stamp (request
decode, handler, admission gate): the timeline's first events inside the
window, median."""
from benchmarks.metrics._lib import events, pct


def read(ctx):
    return pct([(e[6][1] - e[6][0]) * 1e3 for e in events(ctx, "first")
                if e[6][0] is not None and e[6][1] is not None], 50)
