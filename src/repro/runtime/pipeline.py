"""GPipe-style pipeline parallelism over a ``stage`` mesh axis (shard_map +
collective_permute), for homogeneous dense stacks.

Forward schedule: with S stages and M microbatches, run T = M + S - 1
ticks; at tick t, stage s applies its layer block to microbatch (t - s) and
passes the activation ring-wise to stage s+1. Stage s holds the stacked
params slice for its layers only (weight-stationary). This composes with
the TP/data axes of the production mesh — the stage axis can be mapped to
"pod" for cross-pod pipelining where DCN bandwidth favors point-to-point
transfers over gradient all-reduces.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_forward(stage_fn, params_stacked, x, mesh, *,
                     stage_axis: str = "stage", microbatches: int = None):
    """Run ``y = stage_S(...stage_1(x))`` as a pipeline.

    stage_fn(stage_params, x_mb) -> y_mb, applied by each stage to each
    microbatch. params_stacked: pytree with leading dim S (= stage count).
    x: [M, mb, ...] microbatched input. Returns [M, mb, ...] outputs.
    """
    s_count = mesh.shape[stage_axis]
    m = x.shape[0] if microbatches is None else microbatches
    assert x.shape[0] == m

    p_spec = jax.tree.map(lambda _: PS(stage_axis), params_stacked)
    x_spec = PS(None, None)          # microbatch dim replicated per stage

    def shard_fn(p_l, x_all):
        # p_l: this stage's params (leading dim 1) ; x_all: [M, mb, ...]
        sid = jax.lax.axis_index(stage_axis)
        p_mine = jax.tree.map(lambda a: a[0], p_l)
        mb_shape = x_all.shape[1:]
        buf = jnp.zeros(mb_shape, x_all.dtype)      # activation in flight
        outs = jnp.zeros((m,) + mb_shape, x_all.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t; others use the ring buffer
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(sid == 0, x_all[mb_idx], buf)
            active = (t - sid >= 0) & (t - sid < m)
            y = stage_fn(p_mine, x_in)
            y = jnp.where(active, y, buf)
            # last stage commits its finished microbatch
            done_idx = jnp.clip(t - (s_count - 1), 0, m - 1)
            commit = active & (sid == s_count - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(commit, y, outs[done_idx]), done_idx, 0)
            # ring-shift activations to the next stage
            perm = [(i, (i + 1) % s_count) for i in range(s_count)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, m + s_count - 1, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them ring-wise
        outs = jax.lax.ppermute(outs, stage_axis,
                                [(i, (i + 1) % s_count)
                                 for i in range(s_count)])
        outs = jax.lax.psum(
            jnp.where(sid == 0, outs, jnp.zeros_like(outs)), stage_axis)
        return outs

    return jax.shard_map(shard_fn, mesh=mesh,
                         in_specs=(p_spec, x_spec), out_specs=x_spec,
                         check_vma=False)(params_stacked, x)
