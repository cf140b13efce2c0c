"""Host <-> device copies of the kernel entry points, counted by the
recorder (``repro.runtime.tracing``) as ``h2d_bytes`` and ``d2h_bytes``
while it records."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..runtime import tracing


def to_device(x, dtype=None):
    """``jnp.asarray(x, dtype)``; a host array's upload is counted (an
    array already on the device moves nothing)."""
    y = jnp.asarray(x, dtype)
    if isinstance(x, np.ndarray):
        tracing.count("h2d_bytes", y.nbytes)
    return y


def to_host(x) -> np.ndarray:
    """``np.asarray(x)`` of a device array, its download counted."""
    tracing.count("d2h_bytes", x.nbytes)
    return np.asarray(x)


def padded(a, shape, fill=0) -> np.ndarray:
    """``a`` as int32 in the leading corner of an int32 array of
    ``shape`` filled with ``fill``. Kernel entry points pad operands to
    their bucketed shape on the host before the upload, so the device
    sees bucketed shapes only and no padding program compiles per
    length."""
    a = np.asarray(a, np.int32)
    out = np.full(shape, fill, np.int32)
    out[tuple(slice(0, d) for d in a.shape)] = a
    return out
