"""What the readers of the state-space family's layer of two halves
share. The counting and timing helpers are the state-space family's
(imported, not copied); the feed-forward's and the tied head's device
operations are told from the step's other products by the shape of their
first output, the only thing the reduced trace keeps of an operation
beside its name."""
import re

from benchmarks.metrics._nemotron_h import (  # noqa: F401
    live_rows, op_seconds, state_blocks, states_per_step, traced_steps)


def is_family(ctx) -> bool:
    """A program without the layer of two halves (the parent of the PR
    that brought it) has no such field: every reader reads nothing."""
    return bool(ctx.model.get("layer_ffn"))


def shaped_ms(ctx, *widths: int):
    """Device ms a decode step of the operations whose first output is
    [slots, (1,) w] for a ``w`` of ``widths``."""
    if not is_family(ctx):
        return None
    shape = re.compile(r"\[%d,(1,)?(%s)\]" % (
        ctx.slots, "|".join(str(w) for w in widths)))
    steps = traced_steps(ctx)
    s = op_seconds(ctx, lambda n: bool(shape.search(n)))
    return s / steps * 1e3 if steps and s > 0 else None
