"""The jit-compiled training step: microbatched gradient accumulation,
global-norm clipping, AdamW, optional int8 cross-pod gradient compression.

``make_train_step`` closes over static config and returns a function
``(state, batch) -> (state, metrics)`` ready for ``jax.jit`` with the
sharding rules from ``repro.parallel.sharding`` — this is exactly what the
multi-pod dry-run lowers.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import loss_fn
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def init_train_state(cfg: ModelConfig, params: Any) -> dict:
    return {"params": params,
            "opt": init_opt_state(params, cfg.opt_state_dtype)}


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    n_microbatches: int = 1, compress_pod_grads: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).

    batch: dict with (B, T) arrays (tokens / labels) or (B, T, D) embeds.
    With n_microbatches > 1 the batch is split on the leading axis and
    gradients are accumulated in fp32 through a lax.scan — memory-bounded
    gradient accumulation (DP stays on the batch shard; accumulation is
    per-device local).
    """

    def loss_wrap(params, mb):
        loss, metrics = loss_fn(params, cfg, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_wrap, has_aux=True)

    def compute_grads(params, batch):
        if n_microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        def split(x):
            b = x.shape[0]
            return x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])

        mbs = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) /
                               n_microbatches, acc, grads)
            return acc, (loss, metrics)

        grads, (losses, metricses) = jax.lax.scan(body, zero, mbs)
        loss = jnp.mean(losses)
        metrics = jax.tree.map(lambda m: jnp.mean(m), metricses)
        return loss, metrics, grads

    def train_step(state, batch):
        loss, metrics, grads = compute_grads(state["params"], batch)
        if compress_pod_grads:
            from repro.parallel.compress import quantize_dequantize
            # Error-feedback int8 emulation of the cross-pod all-reduce
            # payload (the jit'd collective stays XLA's; payload precision
            # is what compression changes).
            grads = jax.tree.map(
                lambda g: quantize_dequantize(g.astype(jnp.float32))[0].astype(
                    g.dtype), grads)
        with jax.named_scope("optimizer"):
            params, opt, opt_metrics = adamw_update(
                opt_cfg, state["params"], grads, state["opt"])
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return {"params": params, "opt": opt}, metrics

    return train_step
