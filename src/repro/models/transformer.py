"""Block assembly and scan-over-layers.

The layer stack is decomposed into a non-periodic PREFIX (e.g. DeepSeekMoE's
dense first layer) plus a PERIODIC tail: the smallest repeating unit of
(mixer type, is-moe) — one layer for homogeneous stacks, 8 sub-layers for
Jamba's  m m m m a m m m  /  MoE-every-2 pattern.  The tail is a
``jax.lax.scan`` over stacked period params, so the compiled HLO contains
ONE period body regardless of depth — compile times on the 512-device mesh
stay flat in n_layers (the FREP/L0-I$ lesson applied at cluster scale).

Caches (KV for attention, recurrent states for mamba/rwkv) are pytrees with
a leading (n_periods, ...) axis, carried through the same scan and updated
in place.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.parallel import autoshard


@dataclass(frozen=True)
class SubLayer:
    mixer: str                  # 'a' | 'm' | 'r'
    is_moe: bool


def layer_plan(cfg: ModelConfig) -> tuple[list[SubLayer], list[SubLayer], int]:
    """(prefix, period, n_periods)."""
    seq = [SubLayer(cfg.layer_types[i], M.moe_layer_pattern(cfg, i))
           for i in range(cfg.n_layers)]
    # Smallest period wins (maximizes scan reuse); prefix breaks ties
    # (DeepSeekMoE: prefix=1 dense layer + period-1 MoE beats period-28).
    best = None
    for prefix_len in range(0, 2):            # dense-first archs need 1
        tail = seq[prefix_len:]
        if not tail:
            continue
        for p in range(1, len(tail) + 1):
            if len(tail) % p:
                continue
            if all(tail[i] == tail[i % p] for i in range(len(tail))):
                cand = (p, prefix_len)
                if best is None or cand < best[:2]:
                    best = (p, prefix_len, seq[:prefix_len], tail[:p],
                            len(tail) // p)
                break
    if best is not None:
        return best[2], best[3], best[4]
    return seq, [], 0                          # fully explicit fallback


# ---------------------------------------------------------------------------
# one sub-layer
# ---------------------------------------------------------------------------

def init_sublayer(key, cfg: ModelConfig, sub: SubLayer):
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {"norm1": L.init_norm(cfg.norm, cfg.d_model, dt),
         "norm2": L.init_norm(cfg.norm, cfg.d_model, dt)}
    if sub.mixer == "a":
        p["attn"] = A.init_attention(km, cfg)
    elif sub.mixer == "m":
        p["mamba"] = S.init_mamba(km, cfg)
    else:
        p["rwkv"] = S.init_rwkv6(km, cfg)
    if sub.mixer == "r":
        p["cmix"] = S.init_rwkv6_channel_mix(kf, cfg)
    elif sub.is_moe:
        p["moe"] = M.init_moe(kf, cfg)
    else:
        p["ffn"] = L.init_ffn(kf, cfg.d_model, cfg.d_ff, cfg.act, dt)
    return p


def init_sublayer_cache(cfg: ModelConfig, sub: SubLayer, batch: int,
                        max_len: int):
    """Decode-time state for one sub-layer."""
    dt = jnp.dtype(cfg.dtype)
    if sub.mixer == "a":
        return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dt),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dt)}
    if sub.mixer == "m":
        di = cfg.ssm.expand * cfg.d_model
        return {"conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, di), dt),
                "h": jnp.zeros((batch, di, cfg.ssm.d_state), jnp.float32)}
    hs = cfg.ssm.head_dim
    H = cfg.d_model // hs
    return {"x_prev": jnp.zeros((batch, cfg.d_model), dt),
            "S": jnp.zeros((batch, H, hs, hs), jnp.float32),
            "cm_prev": jnp.zeros((batch, cfg.d_model), dt)}


def apply_sublayer(p, cfg: ModelConfig, sub: SubLayer, x, positions,
                   cache=None, cache_index=None, layer=None):
    """returns (x, new_cache, aux_loss).  ``cache`` is the sub-layer's
    decode state, stacked over the layer scan's periods with ``layer`` the
    period (``layer`` None: one layer's state); the new cache is it with
    this step's update written in (``L.cache_write``)."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("norm"):
        h = L.norm(cfg.norm, p["norm1"], x)
    # mamba/rwkv read their O(1) state and write all of it back
    slot = (jax.tree.map(lambda a: L.cache_slot(a, layer), cache)
            if cache is not None and sub.mixer != "a" else None)
    new_cache = new_state = None
    if sub.mixer == "a":
        with jax.named_scope("attention"):
            out, new_cache = A.attention(p["attn"], cfg, h, positions,
                                         kv_cache=cache,
                                         cache_index=cache_index, layer=layer)
    elif sub.mixer == "m":
        state = (slot["conv"], slot["h"]) if slot is not None else None
        with jax.named_scope("ssm"):
            out, (conv, hst) = S.mamba_mix(p["mamba"], cfg, h, state)
        new_state = {"conv": conv, "h": hst} if slot is not None else None
    else:
        state = (slot["x_prev"], slot["S"]) if slot is not None else None
        with jax.named_scope("ssm"):
            out, (xp, st) = S.rwkv6_mix(p["rwkv"], cfg, h, state)
        new_state = ({"x_prev": xp, "S": st, "cm_prev": slot["cm_prev"]}
                     if slot is not None else None)
    x = x + autoshard.barrier(out)

    with jax.named_scope("norm"):
        h = L.norm(cfg.norm, p["norm2"], x)
    x = autoshard.hidden(x)
    if sub.mixer == "r":
        with jax.named_scope("ffn"):
            out, cmp_ = S.rwkv6_channel_mix(
                p["cmix"], cfg, h,
                slot["cm_prev"] if slot is not None else None)
        if new_state is not None:
            new_state = dict(new_state, cm_prev=cmp_)
    elif sub.is_moe:
        with jax.named_scope("moe"):
            out, aux = M.moe_ffn(p["moe"], cfg, h)
    else:
        with jax.named_scope("ffn"):
            out = L.ffn(p["ffn"], h, cfg.act, jnp.dtype(cfg.dtype))
    if new_state is not None:
        new_cache = jax.tree.map(lambda a, u: L.cache_write(a, u, layer),
                                 cache, new_state)
    return autoshard.hidden(x + autoshard.barrier(out)), new_cache, aux


# ---------------------------------------------------------------------------
# the full stack
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig):
    prefix, period, n_periods = layer_plan(cfg)
    kp, ks = jax.random.split(key)
    params = {"prefix": [init_sublayer(k, cfg, sub) for k, sub in
                         zip(jax.random.split(kp, max(1, len(prefix))), prefix)]}
    if n_periods:
        keys = jax.random.split(ks, n_periods)

        def one_period(k):
            kk = jax.random.split(k, len(period))
            return {f"sub{i}": init_sublayer(kk[i], cfg, sub)
                    for i, sub in enumerate(period)}

        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[one_period(k) for k in keys])
        params["periods"] = stacked
    return params


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int):
    prefix, period, n_periods = layer_plan(cfg)
    cache = {"prefix": [init_sublayer_cache(cfg, sub, batch, max_len)
                        for sub in prefix]}
    if n_periods:
        one = {f"sub{i}": init_sublayer_cache(cfg, sub, batch, max_len)
               for i, sub in enumerate(period)}
        cache["periods"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_periods, *a.shape)).copy(), one)
    return cache


def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _row_major(leaf):
    """``leaf`` held in the default (row-major) layout, the one a cache
    has where the program receives and returns it.  Left free, the
    compiler gives a carried KV cache the layout its decode einsums
    prefer, and so relays out the whole cache on the program's entry and
    exit; in this layout the einsums read each layer's slice as it is."""
    return with_layout_constraint(
        leaf, Layout(major_to_minor=tuple(range(leaf.ndim))))


def apply_stack(params, cfg: ModelConfig, x, positions, cache=None,
                cache_index=None):
    """returns (x, new_cache, total_aux)."""
    prefix, period, n_periods = layer_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"prefix": []} if cache is not None else None

    for i, sub in enumerate(prefix):
        c = cache["prefix"][i] if cache is not None else None
        x, nc, aux = apply_sublayer(params["prefix"][i], cfg, sub, x,
                                    positions, c, cache_index)
        aux_total = aux_total + aux
        if cache is not None:
            new_cache["prefix"].append(nc)

    if n_periods and cache is None:
        def period_body(carry, pparams):
            x, aux_acc = carry
            for i, sub in enumerate(period):
                x, _, aux = apply_sublayer(pparams[f"sub{i}"], cfg, sub, x,
                                           positions)
                aux_acc = aux_acc + aux
            return (x, aux_acc), None

        # The scan's own slicing of the stacked weights falls under this
        # scope.
        with jax.named_scope("layer_scan"):
            (x, aux_total), _ = jax.lax.scan(
                _remat_wrap(cfg, period_body), (x, aux_total),
                params["periods"])
    elif n_periods:
        # The stacked caches ride in the carry, not in ``xs``: each
        # sub-layer writes its update into them at its period, so a
        # donated cache is updated in place, never sliced out and
        # restacked.
        def cached_body(carry, scanned):
            x, aux_acc, pcaches = carry
            pparams, layer = scanned
            new = {}
            for i, sub in enumerate(period):
                key = f"sub{i}"
                x, new[key], aux = apply_sublayer(
                    pparams[key], cfg, sub, x, positions, pcaches[key],
                    cache_index, layer)
                aux_acc = aux_acc + aux
            return (x, aux_acc, jax.tree.map(_row_major, new)), None

        with jax.named_scope("layer_scan"):
            (x, aux_total, new_cache["periods"]), _ = jax.lax.scan(
                cached_body, (x, aux_total, cache["periods"]),
                (params["periods"], jnp.arange(n_periods)))
    return x, new_cache, aux_total
