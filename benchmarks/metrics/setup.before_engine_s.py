"""Process start to the engine's first phase: the interpreter, imports, JAX
finding the chip and the harness's own work before ``App()``: the account's
``t_start`` less run.py's ``T0`` (the window's opening less setup_s)."""
from benchmarks.metrics._startup import set_up


def read(ctx):
    acct, end = set_up(ctx)
    if end is None:
        return None
    return acct["t_start"] - (ctx.t_open - ctx.setup_s)
