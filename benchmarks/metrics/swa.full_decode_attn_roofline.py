"""The full layers' decode kernel's share of its roofline in the window
family: live positions (the program's count at dispatch) x 4 KiB a row x
two layers over the chip's bandwidth, or their operations over the matrix
peak if larger, over the kernel's measured time a step."""
from benchmarks.metrics._laguna import FULL_KERNEL, kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, FULL_KERNEL, "full", 2)
