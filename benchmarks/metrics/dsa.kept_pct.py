"""Rows the full layers' selection kept, over the rows it chose among
(the cached rows and each slot's own token), in the window's decode steps:
the program's own two counts. 100 while no context passes ``index_topk``;
the number that says when a cell leaves rows out."""
from benchmarks.metrics._dots3_note import kept_share


def read(ctx):
    share = kept_share(ctx)
    return None if share is None else 100.0 * share
