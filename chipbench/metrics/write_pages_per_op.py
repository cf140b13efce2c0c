"""Maintenance: pages written by flushes and merges per key written in
the window (``IOStats`` ``pages_flushed + pages_merge_written`` over
``entries_written``): the paper's write cost, a count."""


def read(ctx):
    n = ctx.counts["io.entries_written"]
    if n == 0:
        return None
    return (ctx.counts["io.pages_flushed"]
            + ctx.counts["io.pages_merge_written"]) / n
