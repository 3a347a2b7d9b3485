"""What the start-up readers share: the program's own account of its
set-up, ``stats()["startup"]`` of the generator (gofr_tpu/observe/startup.py:
``t_start``, ``t_ready``, ``t_warm`` on the monotonic clock that run.py's
``T0`` and the load generator share, back-to-back ``phases`` with the
chip's memory at each one's end, one ``warmup`` record a program call, the
persistent cache's ``cache`` counts and the names that ``missed``). A
program without the account (the parent of the PR that brought it) gives
None, and every reader then reads nothing."""


def account(ctx):
    return (getattr(ctx, "engine_stats", None) or {}).get("startup")


def set_up(ctx):
    """(the account, the end of set-up on the program's side): the end of
    the first warm-up, or ready where nothing was warmed."""
    acct = account(ctx)
    if not acct or acct.get("t_start") is None:
        return None, None
    return acct, acct.get("t_warm") or acct.get("t_ready")


def phase_seconds(ctx, name):
    """Seconds in the phases called ``name`` up to the end of set-up (a
    later one, a recovery's reallocation or a warm-up while serving, is
    not set-up's)."""
    acct, end = set_up(ctx)
    if end is None:
        return None
    return sum(p["seconds"] for p in acct["phases"]
               if p["name"] == name and p["t0"] < end)


def first_warmup(ctx):
    """The first ``warmup`` phase, or None where no warm-up has ended."""
    acct = account(ctx)
    if not acct or acct.get("t_warm") is None:
        return None
    return next((p for p in acct["phases"]
                 if p["name"] == "warmup" and p.get("pass") == 0), None)
