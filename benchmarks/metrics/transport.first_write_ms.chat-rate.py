"""From the engine's first put to the end of the socket write that carried
the first message (handoff, header encode, the coalesced HEADERS+DATA
write): the timeline's first events inside the window, median. Both stamps
are the program's, taken for the same request."""
from benchmarks.metrics._lib import events, pct


def read(ctx):
    return pct([(e[7][3] - e[6][2]) * 1e3 for e in events(ctx, "first")
                if e[6][2] is not None and e[7][3] is not None], 50)
