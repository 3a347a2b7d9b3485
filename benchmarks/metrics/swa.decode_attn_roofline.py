"""The ring kernel's share of its roofline: the rows of a window layer's
rings the slots held (the program's count, each cursor cut to the window) x
4 KiB a row x six layers over the chip's bandwidth, or their operations
over the matrix peak if larger, over the kernel's measured time a step. The
kernel fetches whole blocks of 256 rows and one row more than it reads, so
the share cannot pass 100."""
from benchmarks.metrics._laguna import RING_KERNEL, kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, RING_KERNEL, "window", 3)
