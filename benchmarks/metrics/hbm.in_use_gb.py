"""Device memory in use when the window closes, on the fullest chip."""


def read(ctx):
    used = [m.get("bytes_in_use") for m in ctx.memory
            if m.get("bytes_in_use") is not None]
    return max(used) / 1e9 if used else None
