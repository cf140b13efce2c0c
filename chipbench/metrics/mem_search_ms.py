"""Read path: host time of the memory component's searches per submit
(the program's ``mem.search`` spans, one per Get batch: one device
search per memory-component table a key may be in), in ms."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.ms_per_submit("mem.search")
