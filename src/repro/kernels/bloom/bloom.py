"""Pallas TPU kernels: Bloom filter build + probe.

TPU adaptation: no scatter/gather by data-dependent addresses (that is a
CUDA idiom). Both directions run through MXU one-hot matmuls over the
filter's factorized [128 rows x W cols] layout, one hash at a time, with
keys as a lane-major [1, K] row and the one-hots transposed ([128, K] row
one-hot R, [W, K] column one-hot C):

  build:  counts += R @ C^T                          (per key-tile, hash)
  probe:  cols = filter @ C ; value = sum(cols * R, axis=0)

The filter stays resident in VMEM across grid steps (accumulator pattern:
initialized at step 0, revisited by every key tile).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import C1, C2


def _hash_onehots(keys, n_slots, w, k_hashes, cols):
    """Per hash j, the transposed row/col one-hots of every key's slot:
    keys [1, K] -> k_hashes pairs ([128, K], [cols, K]) f32.

    Keys stay a lane-major row and each hash is its own [1, K] slot row,
    so no [K, k] -> [K*k] reshape reaches the TPU lowering. ``n_slots``
    and ``w`` are static ints or per-query [1, K] rows; the double hash
    is the reference's int32 arithmetic either way."""
    h1 = (keys * C1) % n_slots
    h2 = ((keys * C2) | 1) % n_slots
    k = keys.shape[-1]
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (128, k), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (cols, k), 0)
    for j in range(k_hashes):
        slots = (h1 + j * h2) % n_slots                          # [1, K]
        yield ((slots // w == r_iota).astype(jnp.float32),
               (slots % w == c_iota).astype(jnp.float32))


def _member(filt, keys, n_slots, w, k_hashes):
    """keys [1, K] against one [128, cols] filter block -> bool [1, K]:
    per hash, the filter column at each key's slot (an MXU one-hot
    matmul), reduced against the row one-hot."""
    filt = filt.astype(jnp.float32)
    member = None
    for oh_r, oh_c in _hash_onehots(keys, n_slots, w, k_hashes,
                                    filt.shape[1]):
        cols = jax.lax.dot(filt, oh_c,
                           precision=jax.lax.Precision.HIGHEST)  # [128, K]
        hit = jnp.sum(cols * oh_r, axis=0, keepdims=True) > 0
        member = hit if member is None else member & hit
    return member


def _build_kernel(keys_ref, filt_ref, *, n_slots, w, k_hashes):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        filt_ref[...] = jnp.zeros_like(filt_ref)

    counts = jnp.zeros(filt_ref.shape, jnp.float32)
    for oh_r, oh_c in _hash_onehots(keys_ref[...], n_slots, w, k_hashes, w):
        counts += jax.lax.dot_general(                           # [128, W]
            oh_r, oh_c, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
    filt_ref[...] += counts.astype(jnp.int32)


def _probe_kernel(keys_ref, filt_ref, out_ref, *, n_slots, w, k_hashes):
    out_ref[...] = _member(filt_ref[...], keys_ref[...], n_slots, w,
                           k_hashes).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_slots", "k_hashes", "tile",
                                   "interpret"))
def build_filter(keys, *, n_slots: int, k_hashes: int = 7, tile: int = 256,
                 interpret: bool = True):
    """keys: [N] (N % tile == 0, pad with a key whose slots you tolerate);
    returns int32 counts [128, n_slots//128]."""
    n = keys.shape[0]
    assert n % tile == 0 and n_slots % 128 == 0
    w = n_slots // 128
    return pl.pallas_call(
        partial(_build_kernel, n_slots=n_slots, w=w, k_hashes=k_hashes),
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((128, w), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((128, w), jnp.int32),
        interpret=interpret,
    )(keys.reshape(1, -1))


def _probe_multi_kernel(keys_ref, ti_ref, ns_ref, w_ref, filt_ref, out_ref,
                        *, k_hashes):
    """One grid step probes one query tile against one table's filter
    block; contributions land only where the query is assigned to that
    table (accumulator over the table axis -- no data-dependent filter
    selection needed)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    member = _member(filt_ref[...], keys_ref[...], ns_ref[...], w_ref[...],
                     k_hashes)
    out_ref[...] += (member & (ti_ref[...] == t)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k_hashes", "tile", "interpret"))
def probe_filters_multi(fstack, keys, ti, nslots, w, *, k_hashes: int = 7,
                        tile: int = 256, interpret: bool = True):
    """fstack [T*128, Wmax] (T filters, columns zero-padded to Wmax);
    keys/ti/nslots/w [K] (K % tile == 0; ti = -1 marks padding) ->
    int32 mask [K]. Grid sweeps (query tile, table); the filter stays
    one [128, Wmax] block per step, so VMEM holds one table's filter at
    a time regardless of tier width."""
    k = keys.shape[0]
    assert k % tile == 0 and fstack.shape[0] % 128 == 0
    t_count = fstack.shape[0] // 128
    wmax = fstack.shape[1]
    row = pl.BlockSpec((1, tile), lambda i, t: (0, i))
    out = pl.pallas_call(
        partial(_probe_multi_kernel, k_hashes=k_hashes),
        grid=(k // tile, t_count),
        in_specs=[row, row, row, row,
                  pl.BlockSpec((128, wmax), lambda i, t: (t, 0))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        interpret=interpret,
    )(keys.reshape(1, -1), ti.reshape(1, -1), nslots.reshape(1, -1),
      w.reshape(1, -1), fstack)
    return out.reshape(-1)


@partial(jax.jit, static_argnames=("k_hashes", "tile", "interpret"))
def probe_filter(filt, keys, *, k_hashes: int = 7, tile: int = 256,
                 interpret: bool = True):
    """filt [128, W]; keys [K] (K % tile == 0) -> int32 mask [K]."""
    k = keys.shape[0]
    assert k % tile == 0
    rows, w = filt.shape
    n_slots = rows * w
    out = pl.pallas_call(
        partial(_probe_kernel, n_slots=n_slots, w=w, k_hashes=k_hashes),
        grid=(k // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                  pl.BlockSpec((128, w), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        interpret=interpret,
    )(keys.reshape(1, -1), filt)
    return out.reshape(-1)
