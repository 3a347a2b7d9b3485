"""Seconds JAX spent in backend compiles, loads from the persistent cache
included, inside the first warm-up: the sum over its records (the program's
own compile clock around each call). setup.warmup_s less this is the time
the programs were traced and lowered in Python and the dummy calls ran;
setup.compile_s (the harness's clock) also counts the compiles of weights,
buffers, the reference check and the probe."""
from benchmarks.metrics._startup import account, first_warmup


def read(ctx):
    if first_warmup(ctx) is None:
        return None
    return sum(r["compile_seconds"] for r in account(ctx)["warmup"]
               if r.get("pass") == 0)
