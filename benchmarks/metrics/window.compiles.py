"""Programs compiled between the window's opening and its close. Must read 0."""


def read(ctx):
    return float(ctx.compile_close["programs"] - ctx.compile_open["programs"])
