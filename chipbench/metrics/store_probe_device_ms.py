"""Read path: device time of the ``_store_probe`` program per Get batch
it served, from the trace, in ms."""
from chipbench.trace import program_time_ns

PROGRAM = "_store_probe"


def read(ctx):
    calls = len(ctx.calls["store_probe_bytes"])
    if ctx.trace is None or not calls:
        return None
    ns = program_time_ns(ctx.trace, PROGRAM)
    return None if ns == 0 else ns / calls / 1e6
