"""Share of the traced seconds in which no operation ran on the device
(averaged over the chips)."""


def read(ctx):
    if not ctx.trace or "busy_s" not in ctx.trace:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
