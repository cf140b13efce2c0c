"""Maintenance: share of the window inside flushes (the program's
``flush.tree`` spans, one per tree flushed), in %."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    if w is None or not w.named("flush.tree"):
        return None
    return w.pct("flush.tree")
