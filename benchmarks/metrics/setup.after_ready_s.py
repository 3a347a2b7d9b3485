"""From the end of the program's set-up (its first warm-up, or ready where
nothing was warmed) to the start of the ramp: the harness's reference check,
the load generator's start and its probes. The window's opening less the
traffic's ``ramp_s`` less the account's ``t_warm``."""
from benchmarks.metrics._startup import set_up


def read(ctx):
    acct, end = set_up(ctx)
    if end is None:
        return None
    return ctx.t_open - float(ctx.traffic.get("ramp_s", 0.0)) - end
