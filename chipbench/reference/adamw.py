"""Plain float32 AdamW with global-norm clipping and a warm-up/cosine
learning rate, written from Loshchilov & Hutter (arXiv:1711.05101): the
moments see the clipped gradient, the update is bias-corrected, and weight
decay is decoupled and applies to matrices only (arrays of two or more
dimensions that are not per-layer gains).  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def lr(o: dict, step: int) -> float:
    """Learning rate of the 1-based optimizer step."""
    if step < o["warmup_steps"]:
        return o["lr"] * step / max(1, o["warmup_steps"])
    t = min(1.0, max(0.0, (step - o["warmup_steps"])
                     / max(1, o["total_steps"] - o["warmup_steps"])))
    return o["lr"] * (o["min_lr_ratio"]
                      + (1 - o["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * t)))


def clip(grads: dict, max_norm: float) -> dict:
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return {k: g * scale for k, g in grads.items()}


def init(params: dict) -> dict:
    return {"m": {k: jnp.zeros_like(p) for k, p in params.items()},
            "v": {k: jnp.zeros_like(p) for k, p in params.items()}}


def update(o: dict, params: dict, grads: dict, state: dict, step, rate,
           decayed) -> tuple[dict, dict]:
    """One step (1-based ``step``, learning rate ``rate``) on clipped
    ``grads``; ``decayed(name)`` says whether a parameter takes weight
    decay."""
    b1, b2 = o["beta1"], o["beta2"]
    new_p, m, v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m[k] = b1 * state["m"][k] + (1 - b1) * g
        v[k] = b2 * state["v"][k] + (1 - b2) * g * g
        u = (m[k] / (1 - b1 ** step)) / (jnp.sqrt(v[k] / (1 - b2 ** step)) + o["eps"])
        if decayed(k):
            u = u + o["weight_decay"] * p
        new_p[k] = p - rate * u
    return new_p, {"m": m, "v": v}


def train(loss_fn, params: dict, batches, o: dict, decayed, norms):
    """Run len(batches) steps of ``loss_fn(params, batch)``.  Returns the
    losses, ``norms`` of the first step's clipped gradients and the final
    parameters."""
    @jax.jit
    def grad(p, batch):
        value, g = jax.value_and_grad(loss_fn)(p, batch)
        return value, clip(g, o["grad_clip"])

    step_fn = jax.jit(
        lambda p, g, s, step, rate: update(o, p, g, s, step, rate, decayed),
        donate_argnums=(0, 2))
    state = init(params)
    losses, first = [], None
    for i, batch in enumerate(batches, start=1):
        value, g = grad(params, batch)
        if first is None:
            first = jax.device_get(jax.jit(norms)(g))
        losses.append(float(value))
        params, state = step_fn(params, g, state, jnp.float32(i),
                                jnp.float32(lr(o, i)))
        del g
    return losses, first, params
