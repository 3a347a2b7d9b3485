"""Device time of the prefill programs in the traced seconds, per thousand
prompt tokens admitted in them (the timeline's prefill events)."""
from benchmarks.metrics._lib import PREFILL_PROGRAMS, events, module_time


def read(ctx):
    count, seconds = module_time(ctx, *PREFILL_PROGRAMS)
    if not count:
        return None
    tokens = sum(e[5] for e in events(ctx, "prefill", ctx.trace["span"]))
    return seconds * 1e3 / (tokens / 1e3) if tokens else None
