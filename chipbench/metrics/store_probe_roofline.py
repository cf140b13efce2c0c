"""Kernels: the store probe's share of its roofline. The least bytes of
the lookups it served (``roofline.lookup_bytes``) over the chip's HBM
bandwidth, divided by the device time of its ``_store_probe`` programs,
in %."""
from chipbench.roofline import roofline_pct
from chipbench.trace import program_time_ns

PROGRAM = "_store_probe"


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    nbytes = sum(ctx.calls["store_probe_bytes"])
    return roofline_pct(nbytes, program_time_ns(ctx.trace, PROGRAM) / 1e9,
                        ctx.peaks["hbm_bytes_per_s"])
