"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis composes with "data" for batch/FSDP sharding, so DCN-crossing
collectives are the gradient reductions only.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``. The model code shards by
    propagation from ``with_sharding_constraint`` hints, which JAX's
    default ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many (host) devices exist — for tests."""
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
