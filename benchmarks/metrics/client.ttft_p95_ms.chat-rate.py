"""Due time to first token at the client, 95th percentile over the requests
due in the window (200 of them: ten beyond it). Read in the traced run: over
two sets of six seeds it spread by 4.3% and 5.6% (PERF.md, PR 23), too wide
for an end-to-end bound of at most 10%."""
from benchmarks.metrics._lib import pct, ttft_ms


def read(ctx):
    return pct(ttft_ms(ctx), 95)
