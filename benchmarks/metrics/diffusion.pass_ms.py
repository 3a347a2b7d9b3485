"""Device time of one pass over the slots' blocks: the decode program's
time in the traced seconds over the passes it holds (``decode_block`` a
dispatch). A pass denoises for some slots and commits for others (slots
are at their own passes, one program runs them all), so the trace does
not tell a denoise pass from a commit pass: one number."""
from benchmarks.metrics._lib import decode_step_s
from benchmarks.metrics._sdar import is_family


def read(ctx):
    s = decode_step_s(ctx) if is_family(ctx) else None
    return None if s is None else s * 1e3
