"""The whole pass's share of its floor, for the block-diffusion family:
the floors of the traced seconds' passes (``roofline_sdar.pass_floor_s``:
the larger of the bytes a pass must move over the bandwidth, weights
once, the head where a slot denoises, the live rows once, and the
operations it must do over the matrix peak, four positions a slot
through eight experts each), over their measured time.
``decode_step_roofline`` counts Mistral's bytes and is not read in this
family's cell."""
from benchmarks import roofline_sdar as rf
from benchmarks.metrics._lib import decode_step_s
from benchmarks.metrics._sdar import is_family, pass_floor_s


def read(ctx):
    if not is_family(ctx):
        return None
    step, floor = decode_step_s(ctx), pass_floor_s(ctx, rf)
    if step is None or floor is None:
        return None
    return 100.0 * floor / step
