"""Kernels: (tier, query) pairs with a covering table that the store
probe tested, per Get submit (the program's ``read.filter_probes``
counter). A program that does not count them gives nothing."""
from chipbench.program import window

COUNTER = "read.filter_probes"


def read(ctx):
    w = window(ctx)
    if w is None or not any(COUNTER in r.counts for r in w.records):
        return None
    return w.per_submit(COUNTER, "gets")
