"""Seconds of the window in which the generation loop stood in one phase
(other than its idle park) for longer than TPU_STALL_MS: the change of
``app_tpu_loop_stall_seconds_total`` over the whole window. 0.0 in a
clean run; a run that reads more took its other numbers from a window
with a silence in it, and its ``server.log`` holds one WARN line a stall
with what the queue and the process's threads did meanwhile (the
``stall`` events in the timeline carry the same records)."""
from benchmarks.metrics._stall import delta


def read(ctx):
    return delta(ctx, "app_tpu_loop_stall_seconds_total")
