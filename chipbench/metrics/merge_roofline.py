"""Kernels: the k-way merge's share of its roofline. The least bytes of
every ``merge_runs`` call the device ran (``roofline.merge_bytes``) over
the chip's HBM bandwidth, divided by the device time of the
``merge_sorted_runs`` programs that started inside those calls, in %."""
from chipbench.roofline import roofline_pct
from chipbench.trace import program_time_ns

PROGRAM = "merge_sorted_runs"


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    nbytes = sum(ctx.calls["merge_bytes"])
    ns = program_time_ns(ctx.trace, PROGRAM,
                         within=ctx.trace.spans("backend.merge_runs"))
    return roofline_pct(nbytes, ns / 1e9, ctx.peaks["hbm_bytes_per_s"])
