"""What the readers of the stall watchdog's series share
(gofr_tpu/observe/stall.py writes them from a thread of its own): the
change of a counter between /metrics at the window's opening and at its
close, so the whole window and not the traced seconds, and None where
the program has no such series (a program without the watchdog, or a
/proc that could not be read): the metric is then left out, not 0."""

from __future__ import annotations


def delta(ctx, name: str) -> float | None:
    if name not in ctx.prom_close:
        return None
    return ctx.prom_close[name] - ctx.prom_open.get(name, 0.0)


def window_pct(ctx, name: str) -> float | None:
    """The counter's seconds as a share of the window's, as read: a
    share of one thread's time over 100 is a counter in the wrong unit
    or of the wrong thread, and has to show (/metrics is read a moment
    after the window closes, which moves a fifth by hundredths of a
    point)."""
    seconds = delta(ctx, name)
    if seconds is None or ctx.seconds <= 0:
        return None
    return 100.0 * seconds / ctx.seconds
