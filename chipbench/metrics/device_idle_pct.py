"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device op intervals / window), in %."""
from chipbench.trace import busy_ns


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window
    return 100.0 * (1.0 - busy_ns(ctx.trace) / (hi - lo))
