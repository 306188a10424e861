"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b-smoke \
      --steps 50 --batch 8 --seq 128 --checkpoint-dir runs/ckpt

Runs on whatever devices exist (host mesh); on a TPU pod slice the same
driver runs the production mesh with --mesh production.  Supports
checkpoint/restart (auto-resumes from the latest step), grad
accumulation, and straggler flagging.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.pipeline import DataConfig, Prefetcher, make_source
from repro.launch import mesh as meshlib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import specs
from repro.models import transformer as tf
from repro.optim import adamw
from repro.train import elastic
from repro.train.checkpoint import Checkpointer
from repro.train.trainer import make_train_step


def main() -> None:
    """CLI driver: train on synthetic data with checkpointing + elasticity."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m-smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="host", choices=["host", "production", "production-multipod"])
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_config(args.arch)
    if args.mesh == "host":
        mesh = meshlib.make_host_mesh()
    else:
        mesh = meshlib.make_production_mesh(
            multi_pod=args.mesh == "production-multipod"
        )
    cfg = specs.resolve_dist(cfg, mesh)
    oc = adamw.OptConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(1, args.steps // 20))

    key = jax.random.PRNGKey(args.seed)
    with jax.sharding.set_mesh(mesh):
        params = init_sharded(cfg, key, mesh)
        opt_state = adamw.init(params)
        step_fn = jax.jit(
            make_train_step(cfg, oc, mesh, accum_steps=args.accum),
            donate_argnums=(0, 1),
        )

        dc = DataConfig(batch=args.batch, seq=args.seq, vocab=cfg.vocab, seed=args.seed)
        source = make_source(dc)

        start = 0
        ckpt = None
        if args.checkpoint_dir:
            ckpt = Checkpointer(args.checkpoint_dir)
            latest = ckpt.latest_step()
            if latest is not None:
                skel = {"params": params, "opt": opt_state}
                restored = ckpt.restore(latest, jax.tree.map(np.asarray, skel))
                params = jax.tree.map(jnp.asarray, restored["params"])
                opt_state = jax.tree.map(jnp.asarray, restored["opt"])
                start = latest
                print(f"resumed from step {latest}")

        prefetch = Prefetcher(source, start_step=start)
        timer = elastic.StepTimer()
        t_start = time.time()
        for _ in range(start, args.steps):
            step_i, batch = prefetch.next()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if cfg.encoder_layers or cfg.n_frontend_tokens:
                batch["frontend"] = jnp.zeros(
                    (args.batch, cfg.n_frontend_tokens, cfg.d_model), jnp.float32
                )
            timer.start()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            straggler = timer.stop()
            if (step_i + 1) % args.log_every == 0 or step_i == start:
                loss = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                print(
                    f"step {step_i+1:5d} loss {loss:8.4f} gnorm {gn:7.3f}"
                    + (" [straggler]" if straggler else ""),
                    flush=True,
                )
            if ckpt and (step_i + 1) % args.checkpoint_every == 0:
                ckpt.save(step_i + 1, {"params": params, "opt": opt_state})
        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state})
            ckpt.wait()
        prefetch.close()
        dt = time.time() - t_start
        n = args.steps - start
        print(f"done: {n} steps in {dt:.1f}s ({dt/max(n,1)*1e3:.0f} ms/step)")


def init_sharded(cfg, key, mesh):
    """Initialize params directly into their mesh shardings (no host copy)."""
    pshard = specs.param_shardings(cfg, mesh)
    init = jax.jit(
        lambda k: tf.init_params(k, cfg), out_shardings=pshard
    )
    return init(key)


if __name__ == "__main__":
    main()
