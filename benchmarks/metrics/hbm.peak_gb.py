"""``peak_bytes_in_use`` when the window closes, on the fullest chip: the
result line's ``memory_peak_bytes`` as a metric, beside hbm.startup_peak_gb."""


def read(ctx):
    peaks = [m.get("peak_bytes_in_use") for m in ctx.memory
             if m.get("peak_bytes_in_use") is not None]
    return max(peaks) / 1e9 if peaks else None
