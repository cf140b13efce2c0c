"""Maintenance: share of the window inside the tick's memory-component
upkeep (the program's ``tick.upkeep`` spans: the partitioned memory
component's merges), in %."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.pct("tick.upkeep")
