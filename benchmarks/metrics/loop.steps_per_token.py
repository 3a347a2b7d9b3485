"""Passes over the stack a decoded token took, by the program's own
count (``stats()["loop"]``: passes / tokens): ``loop_steps`` while every
token runs every pass, and the number that will say when an exit leaves
passes out."""


def read(ctx):
    loop = (ctx.engine_stats or {}).get("loop") or {}
    return loop["passes"] / loop["tokens"] if loop.get("tokens") else None
