"""The prompt programs' share of the device's busy time in the traced
seconds (first device): what a cell of long prompts spends on them, beside
``decode.step_ms`` for the rest. Nothing to read without a trace or where
no prompt program ran in it."""
from benchmarks.metrics._lib import PREFILL_PROGRAMS, module_time


def read(ctx):
    count, seconds = module_time(ctx, *PREFILL_PROGRAMS)
    busy = (ctx.trace or {}).get("busy0_s")
    return 100.0 * seconds / busy if count and busy else None
