"""Top-level models: embedding → stack → norm → readout, plus the loss.

Inputs are a dict ("batch"):
  * LM families:    tokens (B,T) int32 [+ positions (B,T) optional]
  * qwen2-vl:       tokens + positions3 (3,B,T) — M-RoPE streams (the stub
                    vision frontend supplies t=h=w for text-only lowering)
  * hubert (audio): embeds (B,T,D) — precomputed frame embeddings per the
                    task spec (frontend is a stub); labels (B,T) int32

``forward`` covers train/prefill (no cache) and decode (cache + index).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel import autoshard


@dataclass(frozen=True)
class LMModel:
    cfg: ModelConfig


def build_model(cfg: ModelConfig) -> LMModel:
    return LMModel(cfg)


def init_params(cfg: ModelConfig, key) -> dict:
    ke, ks, kh = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    params = {"embed": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, dt),
              "stack": T.init_stack(ks, cfg),
              "final_norm": L.init_norm(cfg.norm, cfg.d_model, dt)}
    if not cfg.tie_embeddings:
        params["head"] = L.init_linear(kh, cfg.d_model, cfg.vocab_size, dt)
    return params


def _positions(cfg: ModelConfig, batch: dict, B: int, T_len: int,
               cache_index=None):
    if cfg.rope == "mrope":
        if "positions3" in batch:
            return batch["positions3"]
        base = jnp.arange(T_len, dtype=jnp.int32)[None].repeat(B, 0)
        if cache_index is not None:
            base = base + cache_index
        return jnp.stack([base, base, base])         # text: t = h = w
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.arange(T_len, dtype=jnp.int32)[None].repeat(B, 0)
    if cache_index is not None:
        pos = pos + cache_index
    return pos


def _readout(params, cfg: ModelConfig, x):
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("readout"):
        if cfg.tie_embeddings:
            return L.unembed(params["embed"], x, dt)
        return L.linear(params["head"], x, dt)


def _cast_once(params, cfg: ModelConfig):
    """Materialize the bf16 working copy of every weight matrix BEFORE the
    layer scan (one local convert per shard) so FSDP all-gathers move bf16,
    not fp32 — §Perf iteration 1.  1-D params (norms, biases) stay fp32;
    the cast is differentiable, so fp32 masters receive exact grads."""
    dt = jnp.dtype(cfg.dtype)
    if jnp.dtype(cfg.param_dtype) == dt:
        return params
    with jax.named_scope("weight_cast"):
        return jax.tree.map(
            lambda p: p.astype(dt) if (p.ndim >= 2 and
                                       p.dtype == jnp.dtype(cfg.param_dtype))
            else p, params)


def forward(params, cfg: ModelConfig, batch: dict, cache=None,
            cache_index=None, logits_mode: str = "all"):
    """returns (logits, new_cache, aux_loss).

    logits_mode: "all" (B,T,V) | "last" (B,1,V — decode/prefill readout) |
    "hidden" (B,T,D — the chunked-CE loss path reads out itself).

    Named scopes (``weight_cast``, ``embed``, ``layer_scan``, ``norm``,
    ``attention``, ``ffn``, ``moe``, ``ssm``, ``readout``, and ``loss`` and
    ``optimizer`` in training) mark each op's model part in the HLO
    metadata, which a device trace carries."""
    params = _cast_once(params, cfg)
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        if cfg.frontend == "audio":
            x = batch["embeds"].astype(dt)
        else:
            x = L.embed(params["embed"], batch["tokens"], dt)
            if cfg.embed_scale:
                x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
        x = autoshard.hidden(x)
    B, T_len = x.shape[:2]
    positions = _positions(cfg, batch, B, T_len, cache_index)

    x, new_cache, aux = T.apply_stack(params["stack"], cfg, x, positions,
                                      cache, cache_index)
    with jax.named_scope("norm"):
        x = L.norm(cfg.norm, params["final_norm"], x)
    if logits_mode == "hidden":
        return x, new_cache, aux
    if logits_mode == "last":
        x = x[:, -1:]
    logits = _readout(params, cfg, x)
    return logits.astype(jnp.float32), new_cache, aux


#: tokens per chunk of the chunked cross-entropy (bounds the (B, chunk, V)
#: logits intermediate — full fp32 (B,T,V) logits would dominate memory at
#: 50k-256k vocabularies).
CE_CHUNK = 256


def _ce_terms(params, cfg: ModelConfig, hidden, targets):
    """(Σ (logz - ll), Σ logz², count) over one chunk; fp32 math on bf16
    logits."""
    logits = autoshard.logits(_readout(params, cfg, hidden)).astype(jnp.float32)
    if cfg.vocab_parallel_ce:
        # Megatron-style: keep logits vocab-sharded; the target log-prob is
        # recovered with a one-hot contraction (a (B,chunk,V)·(B,chunk,V)
        # reduce — sharded over V, psum'd by SPMD as a scalar-sized AR)
        # instead of a take_along_axis gather that forces a V all-gather.
        logz = jax.nn.logsumexp(logits, axis=-1)   # SPMD: per-shard + psum
        onehot = jax.nn.one_hot(targets, cfg.vocab_size, dtype=logits.dtype)
        ll = jnp.sum(logits * onehot, axis=-1)
        return (jnp.sum(logz - ll), jnp.sum(jnp.square(logz)),
                jnp.asarray(targets.size, jnp.float32))
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (jnp.sum(logz - ll), jnp.sum(jnp.square(logz)),
            jnp.asarray(targets.size, jnp.float32))


def loss_fn(params, cfg: ModelConfig, batch: dict,
            aux_weight: float = 0.01, z_weight: float = 1e-4):
    """Next-token (or per-frame, for encoders) cross-entropy + MoE aux +
    z-loss.  CE is computed in T-chunks (checkpointed scan) so the logits
    intermediate never exceeds (B, CE_CHUNK, V).  Returns (loss, metrics)."""
    hidden, _, aux = forward(params, cfg, batch, logits_mode="hidden")
    if cfg.is_encoder_only:
        targets = batch["labels"]
        pred_h = hidden
    else:
        targets = batch["tokens"][:, 1:]
        pred_h = hidden[:, :-1]
    B, T = targets.shape
    chunk = min(CE_CHUNK, T)
    n_chunks, rem = divmod(T, chunk)

    @jax.checkpoint
    def ce_chunk(h, t):
        return _ce_terms(params, cfg, h, t)

    with jax.named_scope("loss"):
        if n_chunks > 1:
            Tm = n_chunks * chunk
            hs = jnp.moveaxis(pred_h[:, :Tm].reshape(B, n_chunks, chunk, -1), 1, 0)
            ts = jnp.moveaxis(targets[:, :Tm].reshape(B, n_chunks, chunk), 1, 0)

            def body(acc, inp):
                nll_s, z_s, cnt = ce_chunk(*inp)
                return (acc[0] + nll_s, acc[1] + z_s, acc[2] + cnt), None

            (nll_sum, z_sum, count), _ = jax.lax.scan(
                body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (hs, ts))
            if rem:
                n2, z2, c2 = ce_chunk(pred_h[:, Tm:], targets[:, Tm:])
                nll_sum, z_sum, count = nll_sum + n2, z_sum + z2, count + c2
        else:
            nll_sum, z_sum, count = ce_chunk(pred_h, targets)

    nll = nll_sum / count
    zloss = z_sum / count
    loss = nll + aux_weight * aux + z_weight * zloss
    return loss, {"nll": nll, "aux": aux, "zloss": zloss,
                  "ppl": jnp.exp(nll)}
