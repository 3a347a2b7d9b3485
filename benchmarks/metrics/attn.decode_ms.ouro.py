"""Device time of the decode kernel (``flash_decode_stacked`` at a group
of one query head a KV head, all ``loop_steps x n_layers`` tables) in one
decode step of the looped family, from the traced seconds."""
from benchmarks.metrics._ouro import DECODE_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, DECODE_KERNEL)
