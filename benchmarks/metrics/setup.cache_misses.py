"""Programs that missed the persistent compile cache from the engine's first
phase to the end of its first warm-up (the program's own count;
``stats()["startup"]["missed"]`` names them, by phase or warm-up call and by
jitted function). On a warm machine the aim is 0."""
from benchmarks.metrics._startup import set_up


def read(ctx):
    acct, end = set_up(ctx)
    if end is None:
        return None
    return float(acct["cache"]["misses"])
