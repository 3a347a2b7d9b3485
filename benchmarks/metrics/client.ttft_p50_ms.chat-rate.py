"""Due time to first token at the client, median over the requests due in
the window (200 of them). Read in the traced run: a request waits behind up
to two in-flight decode blocks, a wait roughly uniform over 0-237 ms, and the
median of 200 such waits spread by 5.4% and 2.4% over two sets of six seeds
(PERF.md, PR 23): too wide to stake an end-to-end bound of at most 10% on."""
from benchmarks.metrics._lib import pct, ttft_ms


def read(ctx):
    return pct(ttft_ms(ctx), 50)
