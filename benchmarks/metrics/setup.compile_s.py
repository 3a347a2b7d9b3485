"""Seconds JAX spent compiling, or loading programs from the persistent
cache, before the window opened."""


def read(ctx):
    return ctx.compile_open["seconds"]
