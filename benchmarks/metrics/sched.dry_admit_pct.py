"""Share of the traced seconds in which the device was dry while the
generation loop was in its admit phase: the timeline's gap events
intersected with its loop events of phase admit, clipped to the traced
span. What admission costs the device, named by the program."""
from benchmarks.metrics._lib import events


def read(ctx):
    if not ctx.trace or "span" not in ctx.trace:
        return None
    a, b = ctx.trace["span"]
    loops = events(ctx, "loop")
    if b <= a or not loops:
        return None  # a program without loop events
    admits = [(max(e[1], a), min(e[1] + e[2], b))
              for e in loops if e[4] == "admit"]
    both = 0.0
    for g in events(ctx, "gap"):
        g0, g1 = g[1], g[1] + g[2]
        both += sum(max(0.0, min(g1, a1) - max(g0, a0)) for a0, a1 in admits)
    return 100.0 * both / (b - a)
