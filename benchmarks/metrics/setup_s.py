"""Process start to the opening of the window: imports, weights, compile or
cache load, warm-up, the reference check, the probe and the ramp."""


def read(ctx):
    return ctx.setup_s
