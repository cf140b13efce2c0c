"""Pallas TPU kernel: batched sorted-tile merge (LSM compaction inner loop).

TPU adaptation (vs a CUDA merge-path kernel): no per-thread pointer
chasing. Each grid step merges one pair of sorted VMEM tiles:

  1. ranks by vectorized cross-tile comparison counts (VPU, 8x128 lanes)
     — ties break toward run A ("newer run wins"),
  2. scatter-by-rank through a one-hot matmul (MXU — the TPU-native way
     to permute data-dependently),
  3. reconciliation keep-mask via a shifted key compare.

The composition of tile merges into full-run compaction (merge-path block
boundaries) happens in ops.py via jnp.searchsorted on tile boundaries; the
kernel does the dense inner work.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _merge_kernel(ka_ref, va_ref, kb_ref, vb_ref, ko_ref, vo_ref, keep_ref):
    ka = ka_ref[...]            # [1, Ba] int32 (sorted)
    kb = kb_ref[...]            # [1, Bb]
    va = va_ref[...]
    vb = vb_ref[...]
    ba = ka.shape[-1]
    bb = kb.shape[-1]
    n = ba + bb
    # ranks: a[i] -> i + #{b < a[i]};  b[j] -> j + #{a <= b[j]}
    rank_a = jnp.sum((kb[:, None, :] < ka[:, :, None]).astype(jnp.int32),
                     axis=-1) + jax.lax.broadcasted_iota(jnp.int32,
                                                         (1, ba), 1)
    rank_b = jnp.sum((ka[:, None, :] <= kb[:, :, None]).astype(jnp.int32),
                     axis=-1) + jax.lax.broadcasted_iota(jnp.int32,
                                                         (1, bb), 1)
    # one-hot scatter via MXU: out[t] = sum_s onehot[s,t] * v[s]
    tgt = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    oh_a = (rank_a[0][:, None] == tgt[0][None, :]).astype(jnp.float32)
    oh_b = (rank_b[0][:, None] == tgt[0][None, :]).astype(jnp.float32)

    def scatter(xa, xb):
        # exact int32 permute via two f32 matmuls (hi/lo 15-bit halves stay
        # well inside f32's 24-bit exact-integer range)
        def halves(x):
            return ((x >> 15).astype(jnp.float32),
                    (x & 0x7FFF).astype(jnp.float32))

        ha, la = halves(xa[0][None, :])
        hb, lb = halves(xb[0][None, :])
        dot = partial(jax.lax.dot, precision=jax.lax.Precision.HIGHEST)
        hi = dot(ha, oh_a) + dot(hb, oh_b)
        lo = dot(la, oh_a) + dot(lb, oh_b)
        return (hi.astype(jnp.int32) << 15) | lo.astype(jnp.int32)

    ko = scatter(ka, kb)
    vo = scatter(va, vb)
    ko_ref[...] = ko
    vo_ref[...] = vo
    prev = jnp.concatenate([ko[:, :1] - 1, ko[:, :-1]], axis=-1)
    keep_ref[...] = (ko != prev).astype(jnp.int32)


@partial(jax.jit, static_argnames=("interpret",))
def merge_tiles(ka, va, kb, vb, *, interpret: bool = True):
    """ka,kb: [G, Ba]/[G, Bb] sorted int32; returns (keys, vals, keep).

    Operands run through the kernel as [G, 1, B] with a squeezed leading
    block dim: each block's last two dims then equal the array's, which
    the TPU lowering requires of a one-row block."""
    g, ba = ka.shape
    bb = kb.shape[1]
    n = ba + bb
    bspec = lambda b: pl.BlockSpec((None, 1, b), lambda i: (i, 0, 0))
    out_shapes = (jax.ShapeDtypeStruct((g, 1, n), jnp.int32),) * 3
    outs = pl.pallas_call(
        _merge_kernel,
        grid=(g,),
        in_specs=[bspec(ba), bspec(ba), bspec(bb), bspec(bb)],
        out_specs=(bspec(n), bspec(n), bspec(n)),
        out_shape=out_shapes,
        interpret=interpret,
    )(ka[:, None], va[:, None], kb[:, None], vb[:, None])
    return tuple(o.reshape(g, n) for o in outs)
