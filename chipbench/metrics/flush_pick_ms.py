"""Maintenance: host time per submit choosing which tree to flush (the
program's ``flush.pick`` spans, one per ranking of the flush policy over
the trees whose memory components hold data), in ms."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    if w is None or not w.named("flush.pick"):
        return None
    return w.ms_per_submit("flush.pick")
