"""Kernels: key-disjoint groups per merge fold (the program's
``merge.groups`` counter over its ``merge.fold`` records): how many
times a fold merges into its accumulated run. A program that does not
count them gives nothing."""
from chipbench.program import window

COUNTER = "merge.groups"


def read(ctx):
    w = window(ctx)
    if w is None:
        return None
    folds = w.named("merge.fold")
    if not any(COUNTER in r.counts for r in folds):
        return None
    return w.total(COUNTER) / len(folds)
