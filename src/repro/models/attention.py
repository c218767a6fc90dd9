"""Attention: MHA / GQA / MQA with RoPE / M-RoPE, qk-norm, causal and
sliding-window masks, KV-cache decode — softmax through the COPIFT kernel
(``repro.kernels.ops.softmax``) when configured.

Layout: q (B, T, H, Dh); kv (B, T, Hkv, Dh); GQA repeats kv groups at use.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.parallel import autoshard

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def init_attention(key, cfg: ModelConfig):
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    d, a = cfg.d_model, cfg.attn_dim
    kv_dim = cfg.n_kv_heads * cfg.d_head
    p = {
        "q": L.init_linear(kq, d, a, dt),
        "k": L.init_linear(kk, d, kv_dim, dt),
        "v": L.init_linear(kv, d, kv_dim, dt),
        "o": L.init_linear(ko, a, d, dt, scale=a ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_norm("rmsnorm", cfg.d_head, dt)
        p["k_norm"] = L.init_norm("rmsnorm", cfg.d_head, dt)
    return p


def _rotate(cfg: ModelConfig, x, positions):
    if cfg.rope == "none":
        return x
    if cfg.rope == "mrope":
        return L.apply_mrope(x, positions, cfg.rope_theta,
                             cfg.mrope_sections)
    if positions.ndim == 3:                   # (3, B, T) given, 1-D wanted
        positions = positions[0]
    return L.apply_rope(x, positions, cfg.rope_theta)


def _softmax(cfg: ModelConfig, scores):
    if cfg.use_copift_softmax:
        return kops.softmax(scores, axis=-1, impl=cfg.softmax_impl)
    return jax.nn.softmax(scores, axis=-1)


def _mask_bias(cfg: ModelConfig, q_len: int, kv_len: int, q_offset,
               dtype) -> jax.Array:
    """(q_len, kv_len) additive mask.  q_offset positions the query block
    inside the kv timeline (decode: q_offset = cache position)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    keep = jnp.ones((q_len, kv_len), bool)
    if cfg.causal:
        keep &= k_pos <= q_pos
    if cfg.sliding_window:
        keep &= k_pos > q_pos - cfg.sliding_window
    return jnp.where(keep, 0.0, NEG_INF).astype(dtype)


#: switch to the chunked (online-softmax) path above this many score elems.
CHUNKED_THRESHOLD = 1 << 23
KV_CHUNK = 1024


def _exp(cfg: ModelConfig, x):
    if cfg.use_copift_softmax:
        from repro.kernels.ref import exp_ref   # the COPIFT construction
        return exp_ref(x)
    return jnp.exp(x)


def _chunk_keep(cfg: ModelConfig, q_pos, k_pos, valid_limit=None):
    keep = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if cfg.causal:
        keep &= k_pos[None, :] <= q_pos[:, None]
    if cfg.sliding_window:
        keep &= k_pos[None, :] > q_pos[:, None] - cfg.sliding_window
    if valid_limit is not None:     # cache: slots beyond the write are junk
        keep &= k_pos[None, :] < valid_limit
    return keep


Q_BLOCK = 1024


def _chunked_attention(cfg: ModelConfig, q, k, v, q_offset, valid_limit=None):
    """FlashAttention-style two-level blocking — the COPIFT Step-4/5
    schedule applied to the score matrix: the (T, S) intermediate is never
    materialized.  The outer scan tiles queries (blocks = Step 4); the inner
    scan streams KV chunks with running (m, l, acc) — multi-buffered spill
    state (Step 5).  Each q-block body is ``jax.checkpoint``-ed so backward
    stores only per-block outputs, not the inner online-softmax carries.

    q: (B,T,Hkv,g,Dh) grouped; k/v: (B,S,Hkv,Dh).  Returns (B,T,Hkv,g,Dh).
    """
    B, T, Hkv, g, Dh = q.shape
    S = k.shape[1]
    C = min(KV_CHUNK, S)
    n_chunks = S // C
    scale = Dh ** -0.5
    Tq = min(Q_BLOCK, T)
    nq = T // Tq
    assert T % Tq == 0, (T, Tq)

    @functools.partial(jax.checkpoint, static_argnums=(2, 3))
    def q_block(qb, qb_pos, lo, hi):
        """qb: (B,Tq,Hkv,g,Dh); qb_pos: (Tq,) absolute positions;
        [lo, hi): STATIC kv-chunk range this block attends (causal /
        sliding-window chunk skipping, §Perf: fully-masked chunks are never
        computed — the scan length itself shrinks)."""
        qf = qb.astype(jnp.float32)

        def body(carry, c):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, c * C, C, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, c * C, C, axis=1)
            s = jnp.einsum("bthgd,bshd->bhgts", qf,
                           kc.astype(jnp.float32)) * scale
            s = autoshard.scores(s)
            k_pos = jnp.arange(C) + c * C
            keep = _chunk_keep(cfg, qb_pos, k_pos, valid_limit)   # (Tq, C)
            s = jnp.where(keep[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))      # (B,Hkv,g,Tq)
            p = jnp.where(keep[None, None, None],
                          _exp(cfg, s - m_new[..., None]), 0.0)
            corr = _exp(cfg, m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgts,bshd->bthgd", p, vc.astype(jnp.float32))
            corr_t = jnp.transpose(corr, (0, 3, 1, 2))       # (B,Tq,Hkv,g)
            acc = acc * corr_t[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, Tq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, Tq), jnp.float32)
        acc0 = jnp.zeros((B, Tq, Hkv, g, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                      jnp.arange(lo, hi))
        denom = jnp.transpose(l, (0, 3, 1, 2))
        return acc / jnp.maximum(denom, 1e-30)[..., None]

    def chunk_range(first_pos: int, last_pos: int) -> tuple[int, int]:
        """STATIC kv-chunk window for q positions [first, last]."""
        if not cfg.causal:
            return 0, n_chunks
        hi = min(last_pos // C + 1, n_chunks)
        lo = 0
        if cfg.sliding_window:
            lo = max(0, (first_pos - cfg.sliding_window + 1) // C)
        return lo, max(hi, lo + 1)

    base = int(q_offset) if not hasattr(q_offset, "aval") else None
    if nq == 1:
        lo, hi = chunk_range(base or 0, (base or 0) + T - 1) \
            if base is not None else (0, n_chunks)
        return q_block(q, jnp.arange(T) + q_offset, lo, hi)

    # Outer q-block loop unrolled with STATIC per-block chunk ranges: the
    # causal lower-left dependence is encoded in scan lengths, not masks.
    qs = q.reshape(B, nq, Tq, Hkv, g, Dh)
    outs = []
    for i in range(nq):
        start = (base or 0) + i * Tq
        lo, hi = chunk_range(start, start + Tq - 1) \
            if base is not None else (0, n_chunks)
        pos = jnp.arange(Tq) + i * Tq + q_offset
        outs.append(q_block(qs[:, i], pos, lo, hi))
    return jnp.stack(outs, axis=1).reshape(B, T, Hkv, g, Dh)


def attention(p, cfg: ModelConfig, x, positions, kv_cache=None,
              cache_index=None, layer=None):
    """x: (B, T, D).  Training: kv_cache None.
    Prefill/decode: kv_cache = dict(k=(B, S, Hkv, Dh), v=...), or those
    stacked over the layer scan's periods with ``layer`` the period;
    cache_index scalar — writes the T new positions at ``cache_index``
    (``L.cache_write``), then attends over the layer's cache read back.
    Returns (out, new_kv_cache)."""
    dt = jnp.dtype(cfg.dtype)
    B, T, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    q = L.linear(p["q"], x, dt).reshape(B, T, H, Dh)
    k = L.linear(p["k"], x, dt).reshape(B, T, Hkv, Dh)
    v = L.linear(p["v"], x, dt).reshape(B, T, Hkv, Dh)
    if cfg.qk_norm:
        q = L.norm("rmsnorm", p["q_norm"], q)
        k = L.norm("rmsnorm", p["k_norm"], k)
    q = _rotate(cfg, q, positions)
    k = _rotate(cfg, k, positions)

    if kv_cache is not None:
        # Write first, then read the layer back: the read then sees only
        # the written cache, which a donated or scan-carried cache keeps
        # in place, and the compiler can fuse it into the einsums below.
        new_cache = {"k": L.cache_write(kv_cache["k"], k, layer, cache_index),
                     "v": L.cache_write(kv_cache["v"], v, layer, cache_index)}
        k = L.cache_slot(new_cache["k"], layer)
        v = L.cache_slot(new_cache["v"], layer)
        q_offset = cache_index
    else:
        new_cache = None
        q_offset = 0

    # GQA: (B, S, Hkv, Dh) → group queries; einsum over grouped heads.
    S = k.shape[1]
    g = H // Hkv
    qg = q.reshape(B, T, Hkv, g, Dh)

    if T > 1 and T * S > CHUNKED_THRESHOLD and S % KV_CHUNK == 0:
        valid = None if kv_cache is None else q_offset + T
        out = _chunked_attention(cfg, qg, k, v, q_offset, valid).astype(dt)
        out = out.reshape(B, T, H * Dh)
        return L.linear(p["o"], out, dt), new_cache

    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k.astype(dt),
                        preferred_element_type=jnp.float32)
    scores = scores * (Dh ** -0.5)
    bias = _mask_bias(cfg, T, S, q_offset, scores.dtype)
    if kv_cache is not None:
        # Mask out cache slots beyond the current position.
        valid = jnp.arange(S)[None, :] <= (q_offset + T - 1)
        bias = bias + jnp.where(valid, 0.0, NEG_INF).astype(scores.dtype)
    scores = scores + bias[None, None, None]
    w = _softmax(cfg, scores).astype(dt)
    out = jnp.einsum("bhgts,bshd->bthgd", w, v.astype(dt))
    out = out.reshape(B, T, H * Dh)
    return L.linear(p["o"], out, dt), new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_attn_layers: int, dtype=None):
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (n_attn_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
