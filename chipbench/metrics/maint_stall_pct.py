"""Maintenance: share of the window spent inside
``MaintenanceScheduler.tick`` (the harness's ``maint.tick`` spans), in %."""
from chipbench.trace import clip, union


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window
    ticks = union(clip(ctx.trace.spans("maint.tick"), lo, hi))
    return 100.0 * sum(e - s for s, e in ticks) / (hi - lo)
