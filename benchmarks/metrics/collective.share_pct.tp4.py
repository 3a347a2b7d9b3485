"""Time in collective operations as a share of the time device 0 was busy,
in the traced seconds."""


def read(ctx):
    if not ctx.trace or "collective_s" not in ctx.trace:
        return None
    return 100.0 * ctx.trace["collective_s"] / ctx.trace["busy0_s"]
