"""Output tokens that reached the clients inside the window, over its
seconds, all chips together."""


def read(ctx):
    return sum(s.get("in_window", 0) for s in ctx.samples) / ctx.seconds
