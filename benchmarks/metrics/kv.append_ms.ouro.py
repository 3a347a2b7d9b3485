"""Device time of the step's write (``append_rows_stacked``, all its
calls: the visit to a slot is cut over the table axis) in one decode step
of the looped family, from the traced seconds. A program that writes by
XLA's scatter has no such kernel and reads nothing."""
from benchmarks.metrics._ouro import APPEND_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, APPEND_KERNEL)
