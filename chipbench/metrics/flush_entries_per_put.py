"""Maintenance: entries flushes wrote to L0 per Put submit (the
program's ``flush.entries`` counter, inside its ``flush.tree`` spans):
how much of each Put the write memory could not absorb."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    if w is None or not w.named("flush.tree"):
        return None
    return w.per_submit("flush.entries", "puts")
