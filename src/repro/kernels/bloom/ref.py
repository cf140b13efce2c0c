"""Pure-jnp oracle for the Bloom filter kernels.

Filter layout: int32 counts [128, W] (unpacked "byte-per-slot" — the TPU
adaptation: VPU-friendly saturating adds instead of read-modify-write bit
ops; packing to bits happens on flush to disk, outside the hot path).
Double hashing: slot_j(key) = (h1 + j*h2) mod n_slots, j = 0..k-1.
"""
from __future__ import annotations

import jax.numpy as jnp

import numpy as np

C1 = np.int32(0x9E3779B1 - 2**32)   # golden-ratio Knuth multiplier (int32)
C2 = np.int32(0x85EBCA77 - 2**32)


def _hashes(keys, n_slots: int, k_hashes: int):
    h1 = (keys * C1) % n_slots
    h2 = ((keys * C2) | 1) % n_slots
    j = jnp.arange(k_hashes, dtype=jnp.int32)
    return (h1[:, None] + j[None, :] * h2[:, None]) % n_slots   # [K, k]


def build_ref(keys, n_slots: int, k_hashes: int = 7):
    """keys: [N] non-negative int32 -> filter counts [128, n_slots//128]."""
    assert n_slots % 128 == 0
    slots = _hashes(keys.astype(jnp.int32), n_slots, k_hashes).reshape(-1)
    flat = jnp.zeros((n_slots,), jnp.int32).at[slots].add(1)
    return flat.reshape(128, n_slots // 128)


def probe_ref(filt, keys, k_hashes: int = 7):
    """filt: [128, W]; keys: [K] -> int32 membership mask [K]."""
    n_slots = filt.shape[0] * filt.shape[1]
    slots = _hashes(keys.astype(jnp.int32), n_slots, k_hashes)   # [K, k]
    vals = filt.reshape(-1)[slots]
    return jnp.all(vals > 0, axis=-1).astype(jnp.int32)


def probe_multi_ref(fstack, keys, ti, nslots, w, k_hashes: int = 7):
    """Fused multi-filter oracle: probe each key against *its own* table's
    filter in a stack of T filters.

    fstack: [T*128, Wmax] -- table t's [128, W_t] filter at rows
    [t*128, (t+1)*128), columns zero-padded to Wmax. Per-query arrays:
    ``ti`` (table index; -1 = padding, always a miss), ``nslots``/``w``
    (that table's slot count and column width). Same double-hash int32
    math as ``probe_ref``, with the modulus taken per-query.
    """
    keys = keys.astype(jnp.int32)
    h1 = (keys * C1) % nslots
    h2 = ((keys * C2) | 1) % nslots
    j = jnp.arange(k_hashes, dtype=jnp.int32)
    slots = (h1[:, None] + j[None, :] * h2[:, None]) % nslots[:, None]
    row = ti[:, None] * 128 + slots // w[:, None]
    col = slots % w[:, None]
    safe = jnp.clip(row, 0, fstack.shape[0] - 1)
    vals = fstack[safe, col]
    return (jnp.all(vals > 0, axis=-1)
            & (ti >= 0)).astype(jnp.int32)
