"""Plain float32 reference of a dense decoder-only transformer.

Covers OLMo (non-parametric LayerNorm, tied embeddings) and Phi-3-mini
(RMSNorm with a gain, untied head): pre-norm blocks of causal multi-head
attention with rotary embeddings (the rotate-half form) and a SwiGLU
feed-forward, a final norm and a linear readout.  Written from the papers'
descriptions in straightforward ``jax.numpy``; it imports nothing of the
program under test.

Every contraction goes through ``ops.einsum`` so that the same code runs at
float32 (``FP32``: ``precision=HIGHEST``) and, as the correctness control,
with every operand rounded to fp8 (``FP8``).

Weights are a flat dict of arrays with the layer axis first (see
:func:`layout`); ``chipbench.weights`` makes them from a seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class _Fp32:
    @staticmethod
    def einsum(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)


def _fp8(x, dtype):
    """Round to fp8 with one scale per tensor (amax maps to the format's
    largest finite value), and back to float32."""
    big = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / big, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return jnp.einsum(spec, _fp8(a, jnp.float8_e4m3fn),
                      _fp8(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _einsum_fp8_fwd(spec, a, b):
    return _einsum_fp8(spec, a, b), (a, b)


def _einsum_fp8_bwd(spec, res, g):
    """fp8 training as usually done: e4m3 operands, e5m2 gradients."""
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn))
    return vjp(_fp8(g, jnp.float8_e5m2))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


class _Fp8:
    einsum = staticmethod(_einsum_fp8)


FP32, FP8 = _Fp32(), _Fp8()


def sizes(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    kv = c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    return d, h, kv, dh, c["intermediate_size"], c["vocab_size"]


def layout(c: dict) -> dict:
    """name -> (shape, scale, offset): weights are offset + scale * N(0, 1).
    Matrices are (d_in, d_out) with the layer axis first."""
    d, h, kv, dh, f, v = sizes(c)
    n = c["num_hidden_layers"]
    out = {
        "embed": ((v, d), d ** -0.5, 0.0),
        "wq": ((n, d, h * dh), d ** -0.5, 0.0),
        "wk": ((n, d, kv * dh), d ** -0.5, 0.0),
        "wv": ((n, d, kv * dh), d ** -0.5, 0.0),
        "wo": ((n, h * dh, d), (h * dh) ** -0.5, 0.0),
        "w_gate": ((n, d, f), d ** -0.5, 0.0),
        "w_up": ((n, d, f), d ** -0.5, 0.0),
        "w_down": ((n, f, d), f ** -0.5, 0.0),
    }
    if not c["tie_word_embeddings"]:
        out["head"] = ((d, v), d ** -0.5, 0.0)
    if c["norm"] == "rmsnorm":
        out["ln1"] = ((n, d), 0.1, 1.0)
        out["ln2"] = ((n, d), 0.1, 1.0)
        out["ln_f"] = ((d,), 0.1, 1.0)
    return out


def _norm(c, x, g):
    eps = c["norm_eps"]
    if c["norm"] == "nonparametric_layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)
    if c["norm"] == "rmsnorm":
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g
    raise ValueError(f"unknown norm {c['norm']!r}")


def _rope(c, x, pos):
    """x (B, T, H, Dh); rotate-half rotary embedding at positions pos (T,)."""
    dh = x.shape[-1]
    inv = 1.0 / (c["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(c, ops, x, lw):
    """One pre-norm block; x (B, T, D) float32, lw this layer's weights."""
    d, h, kv, dh, _, _ = sizes(c)
    b, t, _ = x.shape
    pos = jnp.arange(t)
    y = _norm(c, x, lw.get("ln1"))
    q = ops.einsum("btd,de->bte", y, lw["wq"]).reshape(b, t, h, dh)
    k = ops.einsum("btd,de->bte", y, lw["wk"]).reshape(b, t, kv, dh)
    v = ops.einsum("btd,de->bte", y, lw["wv"]).reshape(b, t, kv, dh)
    q, k = _rope(c, q, pos), _rope(c, k, pos)
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    s = ops.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = ops.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, h * dh)
    x = x + ops.einsum("bte,ed->btd", a, lw["wo"])
    y = _norm(c, x, lw.get("ln2"))
    gate = ops.einsum("btd,df->btf", y, lw["w_gate"])
    up = ops.einsum("btd,df->btf", y, lw["w_up"])
    return x + ops.einsum("btf,fd->btd", jax.nn.silu(gate) * up, lw["w_down"])


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln1", "ln2")


def hidden(w, c, tokens, ops=FP32):
    """Final-normed hidden states (B, T, D) of tokens (B, T)."""
    x = jnp.take(w["embed"], tokens, axis=0)
    layers = {k: w[k] for k in _LAYER_KEYS if k in w}

    @jax.checkpoint
    def body(x, lw):
        return _block(c, ops, x, lw), None

    x, _ = jax.lax.scan(body, x, layers)
    return _norm(c, x, w.get("ln_f"))


def logits(w, c, h, ops=FP32):
    if c["tie_word_embeddings"]:
        return ops.einsum("btd,vd->btv", h, w["embed"])
    return ops.einsum("btd,dv->btv", h, w["head"])


def served_logits(w, c, tokens, prompt_len: int, ops=FP32):
    """Logits (B, n, V) at the positions that predicted the served tokens
    tokens[:, prompt_len:] (teacher-forced on those very tokens)."""
    h = hidden(w, c, tokens[:, :-1], ops)
    return logits(w, c, h[:, prompt_len - 1:], ops)


def loss(w, c, tokens, z_weight: float, ops=FP32):
    """Mean next-token cross-entropy over the batch plus z_weight times the
    mean squared log-partition (the z-loss); the batch is taken one row
    at a time so that one row's activations are live at once."""
    def row(acc, toks):
        h = hidden(w, c, toks[None, :-1], ops)
        z = logits(w, c, h, ops)[0]
        logz = jax.nn.logsumexp(z, -1)
        ll = jnp.take_along_axis(z, toks[1:, None], -1)[:, 0]
        return (acc[0] + jnp.sum(logz - ll), acc[1] + jnp.sum(logz * logz)), None

    zero = jnp.zeros((), jnp.float32)
    (nll, zsq), _ = jax.lax.scan(row, (zero, zero), tokens)
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    return nll / count + z_weight * zsq / count


def decayed(name: str) -> bool:
    """Weight decay takes the matrices, not the norm gains."""
    return not name.startswith("ln")


def stacked(name: str) -> bool:
    """Whether the weight carries the layer axis first."""
    return name not in ("embed", "head", "ln_f")
