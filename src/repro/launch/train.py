"""Training driver: config -> mesh -> (restore) -> loop -> checkpoints.

CPU-scale use (smoke/CI/examples):
  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
      --steps 50 --batch 8 --seq 64 --ckpt /tmp/ck
On a real cluster the same driver runs under the production mesh
(--mesh 16x16 / 2x16x16) with per-host data sharding.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced as make_reduced
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import build_model, init_params, make_shardings
from repro.runtime.checkpoint import Checkpointer
from repro.runtime.elastic import Preemption, StragglerMonitor
from repro.runtime.sharding import activation_sharding, param_rules
from repro.runtime.training import TrainConfig, make_train_step, opt_state_specs


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    return make_mesh(dims, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    mesh = parse_mesh(args.mesh)
    rules = param_rules(fsdp=cfg.fsdp, multi_pod="pod" in mesh.shape)
    model = build_model(cfg)
    pspec = model.param_specs()
    ospec = opt_state_specs(pspec, cfg)
    p_sh = make_shardings(pspec, mesh, rules)
    o_sh = make_shardings(ospec, mesh, rules)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(2, args.steps // 20),
                       microbatches=args.microbatches)
    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, frontend_tokens=cfg.frontend_tokens,
        d_model=cfg.d_model))
    ck = Checkpointer(args.ckpt) if args.ckpt else None
    mon = StragglerMonitor()
    pre = Preemption()

    with mesh, activation_sharding(mesh, rules):
        params = jax.jit(
            lambda k: init_params(pspec, k, cfg.param_dtype),
            out_shardings=p_sh)(jax.random.key(0))
        opt = jax.jit(lambda k: init_params(ospec, k, cfg.optstate_dtype),
                      out_shardings=o_sh)(jax.random.key(1))
        start = 0
        if ck and ck.latest_step() is not None:
            restored, start = ck.restore({"params": params, "opt": opt})
            params, opt = restored["params"], restored["opt"]
            print(f"[train] restored checkpoint at step {start}")
        step_fn = jax.jit(make_train_step(model, tcfg),
                          donate_argnums=(0, 1))
        losses = []
        for step in range(start, args.steps):
            t0 = time.time()
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            params, opt, metrics = step_fn(params, opt, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            if mon.observe(dt):
                print("[train] straggler monitor tripped: checkpoint+restart")
                break
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step={step} loss={loss:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"dt={dt*1e3:.0f}ms")
            if ck and ((step + 1) % args.ckpt_every == 0 or pre.requested):
                ck.save(step + 1, {"params": params, "opt": opt})
            if pre.requested:
                print("[train] preemption requested: exiting cleanly")
                break
        if ck:
            ck.save(args.steps, {"params": params, "opt": opt})
            ck.wait()
        print(f"[train] done. first loss={losses[0]:.4f} "
              f"last loss={losses[-1]:.4f}")
        return losses


if __name__ == "__main__":
    main()
