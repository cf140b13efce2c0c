"""Backend: bytes uploaded from host to device per submit (the
program's ``h2d_bytes`` counter at every host array its kernels take),
in MB of 10^6 bytes."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    if w is None:
        return None
    n = w.per_submit("h2d_bytes")
    return None if n is None else n / 1e6
