"""Share of the traced seconds in which the program itself knew the device
dry: the timeline's gap events (generation loop: the last program queued
was seen finished, until the next dispatch of any program), clipped to the
traced span. The program's own device.idle_pct: the two are read over the
same seconds."""
from benchmarks.metrics._lib import events


def read(ctx):
    if not ctx.trace or "span" not in ctx.trace:
        return None
    a, b = ctx.trace["span"]
    if b <= a or not events(ctx, "decode"):
        return None  # the program's timeline is off
    dry = sum(max(0.0, min(e[1] + e[2], b) - max(e[1], a))
              for e in events(ctx, "gap"))
    return 100.0 * dry / (b - a)
