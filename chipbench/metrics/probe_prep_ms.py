"""Read path: host time per submit that the store probe spends before
its dispatch (the program's ``read.probe_prep`` spans: per-tier table
assignment, the ``[tables, K]`` host tables and their upload), in ms."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.ms_per_submit("read.probe_prep")
