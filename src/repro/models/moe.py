"""Mixture-of-Experts block with expert parallelism.

Experts are sharded over the ``model`` mesh axis. Activations on the
residual stream are replicated across ``model`` (megatron-TP layout), so
dispatch needs **no all-to-all**: every model shard routes the full token
set to its local experts (capacity-bounded, sort-based dispatch with static
shapes), and a single psum over ``model`` combines expert outputs — the
same collective a dense TP FFN needs. Used by arctic-480b (top-2 of 128 +
dense residual) and granite-moe (top-8 of 32).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .layers import P


def moe_spec(cfg) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = d ** -0.5
    spec = {
        "router": P((d, e), ("embed", "experts_r"), scale=s),
        "wi_gate": P((e, d, ff), ("experts", "embed", "mlp"), scale=s),
        "wi_up": P((e, d, ff), ("experts", "embed", "mlp"), scale=s),
        "wo": P((e, ff, d), ("experts", "mlp", "embed"), scale=ff ** -0.5),
    }
    return spec


def _capacity(cfg, tokens: int) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor
              / max(cfg.num_experts, 1))
    return max(8, -(-cap // 8) * 8)        # round up to a multiple of 8


def _moe_local(cfg, p, x, e_start: int, e_local: int):
    """Per-shard MoE: route all local tokens to the shard's experts.

    x: [T, d] (this shard's tokens, replicated over the model axis).
    Returns this shard's contribution [T, d] (sum over shards = full MoE).
    """
    t, d = x.shape
    k = cfg.top_k
    cap = _capacity(cfg, t)
    logits = jnp.einsum("td,de->te", x, p["router"].astype(x.dtype))
    gates_full = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_k, eid_k = jax.lax.top_k(gates_full, k)                # [T,k]
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)
    eid = eid_k.reshape(-1)                                     # [T*k]
    gate = gate_k.reshape(-1).astype(x.dtype)
    tok = jnp.repeat(jnp.arange(t), k)
    le = eid - e_start
    valid = (le >= 0) & (le < e_local)
    le_sort = jnp.where(valid, le, e_local)                     # invalid last
    order = jnp.argsort(le_sort, stable=True)
    le_s, tok_s, gate_s = le_sort[order], tok[order], gate[order]
    # position of each pair within its expert segment
    seg_start = jnp.searchsorted(le_s, jnp.arange(e_local + 1))
    pos = jnp.arange(t * k) - seg_start[le_s]
    keep = (le_s < e_local) & (pos < cap)
    slot = jnp.where(keep, le_s * cap + pos, e_local * cap)     # drop slot
    # Receive-side dispatch: scatter only int32 indices, then gather rows —
    # avoids materializing a [t*k, d] send buffer.
    src = jnp.full((e_local * cap + 1,), t, jnp.int32).at[slot].set(tok_s)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], 0)
    h = x_pad[src[:-1]].reshape(e_local, cap, d)
    g = jnp.einsum("ecd,edf->ecf", h, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", h, p["wi_up"].astype(x.dtype))
    o = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   p["wo"].astype(x.dtype))
    o_flat = jnp.concatenate([o.reshape(e_local * cap, d),
                              jnp.zeros((1, d), x.dtype)], 0)
    y = jnp.zeros((t, d), x.dtype).at[tok_s].add(
        o_flat[slot] * gate_s[:, None])
    return y


def moe_block(cfg, p, x, mesh=None, data_axes=("data",), dense_mlp=None):
    """x: [B,S,d]. With a mesh: shard_map over (data..., model); experts are
    split over ``model`` and outputs psum-combined. Without a mesh: single
    shard holding all experts (smoke tests).

    ``dense_mlp`` (arctic's dense-residual FFN params) may be passed to
    compute the TP MLP *inside* the same shard_map so its reduction fuses
    into the MoE psum — one all-reduce per layer instead of two (perf
    knob ``fuse_moe_dense_ar``)."""
    b, s, d = x.shape
    if mesh is None or "model" not in mesh.shape:
        y = _moe_local(cfg, p, x.reshape(b * s, d), 0, cfg.num_experts)
        y = y.reshape(b, s, d)
        if dense_mlp is not None:
            from .layers import mlp
            y = y + mlp(dense_mlp, x, x.dtype)
        return y
    m = mesh.shape["model"]
    e_local = cfg.num_experts // m
    data_axes = tuple(a for a in data_axes if a in mesh.shape)

    # params: experts sharded over model on axis 0; router replicated
    pspec = {"router": PS(), "wi_gate": PS("model"), "wi_up": PS("model"),
             "wo": PS("model")}
    xspec = PS(data_axes)                 # batch sharded, model-replicated
    specs = (pspec, xspec)
    args = (p, x)
    if dense_mlp is not None:
        specs += ({"wi_gate": PS(None, "model"), "wi_up": PS(None, "model"),
                   "wo": PS("model", None)},)
        args += (dense_mlp,)

    def shard_fn(p_l, x_l, *rest):
        ax = jax.lax.axis_index("model")
        bl = x_l.shape[0] * x_l.shape[1]
        y = _moe_local(cfg, p_l, x_l.reshape(bl, d), ax * e_local, e_local)
        y = y.reshape(x_l.shape)
        if rest:                          # dense-residual partial sums
            from .layers import mlp
            y = y + mlp(rest[0], x_l, x_l.dtype)
        return jax.lax.psum(y, "model")   # ONE fused reduction

    return jax.shard_map(shard_fn, mesh=mesh,
                         in_specs=specs, out_specs=xspec,
                         check_vma=False)(*args)
