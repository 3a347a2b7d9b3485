"""Share of the traced seconds in which the generation loop's thread had
work of its own: everything but its wait (device busy, nothing to admit),
fetch (blocked on the device-to-host copy) and park (idle server) phases,
from the timeline's loop events clipped to the traced span."""
from benchmarks.metrics._lib import events

IDLE = ("wait", "fetch", "park")


def read(ctx):
    if not ctx.trace or "span" not in ctx.trace:
        return None
    a, b = ctx.trace["span"]
    loops = events(ctx, "loop")
    if b <= a or not loops:
        return None  # a program without loop events
    idle = sum(max(0.0, min(e[1] + e[2], b) - max(e[1], a))
               for e in loops if e[4] in IDLE)
    return 100.0 * (1.0 - idle / (b - a))
