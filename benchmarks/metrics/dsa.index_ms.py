"""Device time of the indexer's score pass over the cached index keys in
one decode step (ops/dsa.py's kernel ``index_scores_stacked``, all three
full layers), from the traced seconds. The top-k beside it (ops/dsa.py's
bisection: 32 counts a layer and the mask, 0.07 ms a step on the chip,
PERF.md, Findings PR 46) is XLA fusions under the ``dsa/top_k`` scope,
which the trace's reduction does not keep (PERF.md 7d(a)): a reader
could find them only by guessing at their shapes, so they are left out
until a name reaches the trace."""
from benchmarks.metrics._dots3_note import SCORE_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, SCORE_KERNEL)
