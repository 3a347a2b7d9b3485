"""The whole decode step's share of its roofline, for the state-space
family's layer of two halves: every weight once (36 mamba and 4 attn
mixers, the gated feed-forward of all 40 layers, the tied table for the
logits), the active states and tails read and written once (the
program's count), and the live K and V rows of the attn layers, over the
chip's peak bandwidth, over the measured step. ``decode_step_roofline``
counts Mistral's bytes and ``.nemotron_h`` no feed-forward and no tied
table: neither is read in this cell."""
from benchmarks import roofline_granite_hybrid as rf
from benchmarks.metrics._granite_hybrid import (is_family, live_rows,
                                                 states_per_step)
from benchmarks.metrics._lib import decode_step_s


def read(ctx):
    if not is_family(ctx):
        return None
    step, rows, states = decode_step_s(ctx), live_rows(ctx), \
        states_per_step(ctx)
    if None in (step, rows, states) or ctx.peaks is None:
        return None
    return 100.0 * rf.step_bytes(ctx.model, states, rows) \
        / ctx.peaks["hbm_bytes_per_s"] / step
