"""Shared neural-net layers (pure functions over param dicts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .params import P


def softcap(x, cap: float):
    return jnp.where(cap > 0, cap * jnp.tanh(x / jnp.maximum(cap, 1e-6)), x) \
        if cap else x


# -- RMSNorm -----------------------------------------------------------------
def rmsnorm_spec(d: int) -> dict:
    return {"scale": P((d,), ("embed",), init="ones")}


def rmsnorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


# -- gated MLP (SwiGLU) --------------------------------------------------------
def mlp_spec(d: int, ff: int) -> dict:
    s = d ** -0.5
    return {
        "wi_gate": P((d, ff), ("embed", "mlp"), scale=s),
        "wi_up": P((d, ff), ("embed", "mlp"), scale=s),
        "wo": P((ff, d), ("mlp", "embed"), scale=ff ** -0.5),
    }


def mlp(p, x, compute_dtype):
    g = jnp.einsum("...d,df->...f", x, p["wi_gate"].astype(compute_dtype))
    u = jnp.einsum("...d,df->...f", x, p["wi_up"].astype(compute_dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(compute_dtype))


def mlp_psum_bf16(p, x, compute_dtype, mesh, data_axes=("pod", "data")):
    """Manual-collective TP MLP: shard_map over the model axis with an
    explicit bf16 psum. GSPMD's auto-partitioned path all-reduces the f32
    dot accumulator; reducing in bf16 halves the dominant TP collective."""
    from jax.sharding import PartitionSpec as PS
    dp = tuple(a for a in data_axes if a in mesh.shape)
    pspec = {"wi_gate": PS(None, "model"), "wi_up": PS(None, "model"),
             "wo": PS("model", None)}
    xspec = PS(dp)

    def fn(p_l, x_l):
        y = mlp(p_l, x_l, compute_dtype).astype(jnp.bfloat16)
        return jax.lax.psum(y, "model").astype(compute_dtype)

    return jax.shard_map(fn, mesh=mesh, in_specs=(pspec, xspec),
                         out_specs=xspec, check_vma=False)(p, x)


# -- embeddings (tied; gemma-style sqrt(d) input scaling keeps both the
# embedding output and the tied-unembed logits at unit variance) -------------
def embed_spec(vocab: int, d: int) -> dict:
    return {"table": P((vocab, d), ("vocab", "embed"), scale=d ** -0.5)}


def embed(p, tokens, compute_dtype):
    d = p["table"].shape[-1]
    return p["table"].astype(compute_dtype)[tokens] * (d ** 0.5)


def unembed(p, x, compute_dtype):
    return jnp.einsum("...d,vd->...v", x, p["table"].astype(compute_dtype))


# -- rotary position embedding ----------------------------------------------------
def rope(x, positions, theta: float = 10_000.0):
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32)
                    / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]                        # [..., S, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)
