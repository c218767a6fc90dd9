"""Primitive layers: linear, norms, rotary embeddings, activations,
embedding tables.  Functional style: ``init_*`` builds param subtrees,
``apply`` functions are pure.

Conventions:
* params are stored in ``cfg.param_dtype`` (fp32 master by default) and cast
  to ``cfg.dtype`` (bf16) at use — mixed-precision training;
* every init takes an explicit ``jax.random.PRNGKey``;
* weight layouts are (d_in, d_out) so TP sharding specs read naturally.

Cache leaves are one layer's state, (B, ...), or a stack of them over the
layer scan's periods, (n_periods, B, ...); ``cache_slot`` and
``cache_write`` take ``layer`` None for the first and the period index for
the second.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def _truncnorm(key, shape, scale, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# linear / embedding
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, dtype, bias: bool = False,
                scale: float | None = None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": _truncnorm(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x, dtype):
    y = x.astype(dtype) @ p["w"].astype(dtype)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


def init_embedding(key, vocab: int, d: int, dtype):
    return {"table": _truncnorm(key, (vocab, d), d ** -0.5, dtype)}


def embed(p, ids, dtype):
    return jnp.take(p["table"].astype(dtype), ids, axis=0)


def unembed(p, x, dtype):
    """Tied readout: logits = x @ tableᵀ."""
    return x.astype(dtype) @ p["table"].astype(dtype).T


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(kind: str, d: int, dtype):
    if kind == "nonparam_ln":                 # OLMo: no learned affine
        return {}
    if kind == "layernorm":
        return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"g": jnp.ones((d,), dtype)}       # rmsnorm / gemma_rmsnorm


def norm(kind: str, p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind in ("layernorm", "nonparam_ln"):
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            y = y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)
        return y.astype(x.dtype)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    g = p["g"].astype(jnp.float32)
    if kind == "gemma_rmsnorm":               # gemma scales by (1 + g)
        y = y * (1.0 + g)
    else:
        y = y * g
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and qwen2-vl's M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, Dh); positions: (B, T) int32."""
    d_head = x.shape[-1]
    inv = rope_freqs(d_head, theta)                       # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, T, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: tuple[int, int, int]) -> jax.Array:
    """qwen2-vl M-RoPE: the Dh/2 frequency slots are split into (t, h, w)
    sections, each rotated by its own position stream.

    x: (B, T, H, Dh); positions3: (3, B, T) — temporal, height, width.
    For text tokens the three streams are equal (the stub frontend supplies
    t=h=w), reducing exactly to 1-D RoPE.
    """
    d_head = x.shape[-1]
    inv = rope_freqs(d_head, theta)                       # (Dh/2,)
    sec = np.asarray(sections)
    assert sec.sum() == d_head // 2, (sections, d_head)
    sel = np.repeat(np.arange(3), sec)                    # (Dh/2,) section id
    pos = jnp.take(positions3, jnp.asarray(sel), axis=0)  # (Dh/2, B, T)
    ang = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * inv
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / gated FFN
# ---------------------------------------------------------------------------

def act_fn(kind: str, x):
    if kind in ("swiglu", "silu"):
        return jax.nn.silu(x)
    # geglu / gelu: gemma uses tanh-approximated GELU.
    return jax.nn.gelu(x, approximate=True)


def init_ffn(key, d_model: int, d_ff: int, act: str, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    gated = act in ("swiglu", "geglu")
    p = {"up": init_linear(k1, d_model, d_ff, dtype),
         "down": init_linear(k2, d_ff, d_model, dtype,
                             scale=d_ff ** -0.5)}
    if gated:
        p["gate"] = init_linear(k3, d_model, d_ff, dtype)
    return p


def ffn(p, x, act: str, dtype):
    up = linear(p["up"], x, dtype)
    if "gate" in p:
        up = up * act_fn(act, linear(p["gate"], x, dtype))
    else:
        up = act_fn(act, up)
    return linear(p["down"], up, dtype)


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------

def cache_slot(leaf, layer=None):
    """One layer's state of a cache leaf: period ``layer`` of a stacked
    leaf, or the leaf itself when ``layer`` is None."""
    return leaf if layer is None else leaf[layer]


def cache_write(leaf, update, layer=None, pos=0):
    """``leaf`` with ``update`` written at ``pos`` along the layer state's
    axis 1 (the sequence axis of a KV cache) and, for a stacked leaf, at
    period ``layer``.  A dynamic-update-slice: in place when the leaf is
    donated or carried through a scan, so only ``update`` is written."""
    lead = () if layer is None else (layer,)
    start = lead + (0, pos) + (0,) * (update.ndim - 2)
    update = update.reshape((1,) * len(lead) + update.shape)
    return jax.lax.dynamic_update_slice(leaf, update.astype(leaf.dtype), start)
