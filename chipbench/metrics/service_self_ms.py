"""Front door and planner: host time per submit outside the store.

The harness's ``submit`` span less the part its child store-call spans
(``store.read``, ``store.write``, ``maint.tick``) cover, averaged over
the window's submits, in ms."""
from chipbench.trace import clip, union

CHILDREN = ("store.read", "store.write", "maint.tick")


def read(ctx):
    if ctx.trace is None:
        return None
    submits = ctx.trace.spans("submit")
    if not submits:
        return None
    kids = [iv for name in CHILDREN for iv in ctx.trace.spans(name)]
    self_ns = 0
    for s, e in submits:
        covered = sum(b - a for a, b in union(clip(kids, s, e)))
        self_ns += (e - s) - covered
    return self_ns / len(submits) / 1e6
