"""Decode blocks that were reaped with the next block already queued behind
them on the device stream, as a share of the decode blocks reaped in the
traced seconds. A decode event runs from a block's dispatch to the end of
its reap (the fetch of its tokens), and the loop dispatches and reaps in
order: the next block was queued behind this one exactly when its event
starts before this one ends. Behind such a reap the device computes while
the host fetches, delivers and admits; behind any other it is dry until
the next dispatch. The program's own count is
``stats()["scheduler"]["pipeline"]`` (``overlapped_reaps`` over ``reaps``),
since its start; this is the same quantity over the traced span."""
from benchmarks.metrics._lib import events


def read(ctx):
    if not ctx.trace or "span" not in ctx.trace:
        return None
    a, b = ctx.trace["span"]
    blocks = sorted((e[1], e[1] + e[2]) for e in events(ctx, "decode"))
    reaped = overlapped = 0
    for i, (_, end) in enumerate(blocks):
        if not a <= end < b:
            continue
        reaped += 1
        overlapped += i + 1 < len(blocks) and blocks[i + 1][0] < end
    if not reaped:
        return None
    return 100.0 * overlapped / reaped
