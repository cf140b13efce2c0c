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
