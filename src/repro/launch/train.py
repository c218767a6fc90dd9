"""End-to-end training driver.

Wires the whole substrate: config registry → param init (sharded via the
rule table when a mesh is requested) → deterministic xoshiro data pipeline →
jit'd train step (microbatching, AdamW, clipping) → checkpoint manager with
async saves, crash-resume, and straggler monitoring.

Laptop-scale run (the examples use this):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --variant smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

One TPU v5e at OLMo-1B widths, depth cut to 8 layers so the state fits:
  PYTHONPATH=src python -m repro.launch.train --variant full --layers 8 \
      --steps 3 --batch 8 --seq 512

This script trains on one device.  The sharded step (``ShardingRules`` on a
mesh) is lowered by ``repro.launch.dryrun`` and run on four chips by
``chip_smoke.py --chips 4``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, load_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.train.fault import CheckpointManager, StragglerMonitor
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def jit_train_step(cfg, opt_cfg: AdamWConfig, n_microbatches: int = 1):
    """``make_train_step`` jitted with its state donated: the output state
    reuses the input's buffers, so only one copy of params + optimizer
    state is live."""
    return jax.jit(make_train_step(cfg, opt_cfg, n_microbatches=n_microbatches),
                   donate_argnums=0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--variant", choices=["full", "smoke"], default="smoke")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers (0: all)")
    ap.add_argument("--softmax-impl", choices=["auto", "pallas", "reference"],
                    default="auto")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = load_config(args.arch, args.variant).replace(
        softmax_impl=args.softmax_impl)
    if args.layers:
        print(f"[config] {cfg.name}: depth cut to {args.layers} of "
              f"{cfg.n_layers} layers, widths unchanged")
        cfg = cfg.with_depth(args.layers)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    pipe = TokenPipeline(cfg, shape, PipelineConfig(seed=args.seed + 1))
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 10))
    step_fn = jit_train_step(cfg, opt_cfg, args.microbatches)

    def init_fn():
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        return init_train_state(cfg, params)

    manager = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if manager is not None:
        like = jax.eval_shape(init_fn)
        state, start_step = manager.restore_or_init(like, init_fn)
        if start_step:
            print(f"[resume] from step {start_step}")
    else:
        state = init_fn()

    monitor = StragglerMonitor()
    history = []
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = pipe.host_batch_at(step)
        state, metrics = step_fn(state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.time() - t0
        flagged = monitor.record(f"host{jax.process_index()}", step, dt)
        history.append(dict(step=step, seconds=dt, straggler=flagged,
                            **metrics))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={metrics['loss']:.4f} "
                  f"nll={metrics['nll']:.4f} lr={metrics['lr']:.2e} "
                  f"gnorm={metrics['grad_norm']:.2f} {dt*1e3:.0f}ms",
                  flush=True)
        if manager is not None and (step + 1) % args.ckpt_every == 0:
            manager.save(step + 1, state)
    if manager is not None:
        manager.save(args.steps, state)
        manager.wait()
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)
    final = history[-1]["loss"] if history else float("nan")
    first = history[0]["loss"] if history else float("nan")
    print(f"[done] steps={args.steps} loss {first:.4f} -> {final:.4f}")
    return history


if __name__ == "__main__":
    main()
