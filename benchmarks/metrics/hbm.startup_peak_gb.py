"""``peak_bytes_in_use`` of the fullest chip at the end of the first warm-up,
from the program's account. Equal to hbm.peak_gb: set-up sets the process's
memory peak (the account's phases and warm-up records say which step first
shows it); smaller: serving does."""
from benchmarks.metrics._startup import first_warmup


def read(ctx):
    phase = first_warmup(ctx)
    if not phase or phase.get("peak_bytes") is None:
        return None
    return phase["peak_bytes"] / 1e9
