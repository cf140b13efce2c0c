"""Maintenance: share of the window inside the tick's disk merge pass
(the program's ``tick.merge`` spans), in %."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.pct("tick.merge")
