"""Read path: host time per submit replaying the staged path's page
pins after the store probe (the program's ``read.pin_replay`` spans),
in ms."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.ms_per_submit("read.pin_replay")
