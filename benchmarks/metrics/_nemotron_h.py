"""What the state-space family's readers share: the decode blocks' counts
of states updated from the program's timeline, the two kernels' names in
a device trace, the routed experts' operations by the dispatch's own
width. Counting and timing helpers are the latent and the hybrid
families' (imported, not copied)."""
import re

from benchmarks.metrics._deepseek_v3 import (  # noqa: F401
    live_rows, moe_blocks, op_seconds, per_step_mean, traced_steps)
from benchmarks.metrics._solar_open2 import (  # noqa: F401
    kernel_seconds, prefilled_tokens, state_blocks, states_per_step)

# the names the device trace gives ops/ssd.py's kernels (their jitted
# functions)
DECODE_KERNEL = "ssd_decode"
PREFILL_KERNEL = "ssd_prefill"


def is_family(ctx) -> bool:
    """A program without the family (the parent of the PR that brought
    it) has no such field: every reader then reads nothing."""
    return "mamba" in (ctx.model.get("layer_pattern") or ())


def expert_seconds(ctx) -> float:
    """Device seconds of the routed experts' dispatch and matmuls, as
    ``_deepseek_v3.expert_seconds`` finds them (operations one dispatch
    block or the whole padded buffer tall), but as wide as the program
    says it dispatches (``stats()["moe_decode_dispatch"]["width"]``: the
    latent, not the model) or as the experts: a program that does not
    say a width is not this family's."""
    said = (ctx.engine_stats or {}).get("moe_decode_dispatch") or {}
    if "width" not in said:
        return 0.0
    shape = re.compile(r"\[(%d|%d),(%d|%d)\]" % (
        said["block_rows"], said["buffer_rows"], ctx.model["moe_ffn_dim"],
        said["width"]))
    return op_seconds(ctx, lambda n: bool(shape.search(n)))
