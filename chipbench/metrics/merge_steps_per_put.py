"""Kernels: pairwise device merges per Put submit (the program's
``merge.steps`` counter: a fold of k runs takes k - 1)."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.per_submit("merge.steps", "puts")
