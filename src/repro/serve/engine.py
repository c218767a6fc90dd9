"""Serving: prefill + single-token decode steps (what the decode_32k /
long_500k dry-run cells lower), and a batched generation engine.

The decode step is ONE new token against a seq_len-deep cache: attention
layers read/write the KV cache at ``cache_index``; mamba/rwkv layers carry
O(1) recurrent state (why the SSM/hybrid archs own the 500k cell).
Sampling uses the paper's xoshiro128+ kernel — even the serving path runs
COPIFT machinery.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.model import compute_weights, forward
from repro.models.transformer import init_stack_cache
from repro.obs import metrics as _obs_metrics
from repro.obs.spans import span as _obs_span


def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    return init_stack_cache(cfg, batch, max_len)


def make_serve_step(cfg: ModelConfig):
    """serve_step(params, cache, tokens (B,1), cache_index) →
    (logits (B,V), new_cache)."""

    def serve_step(params, cache, tokens, cache_index):
        logits, new_cache, _ = forward(params, cfg, {"tokens": tokens},
                                       cache=cache, cache_index=cache_index,
                                       logits_mode="last")
        return logits[:, 0], new_cache

    return serve_step


def make_prefill(cfg: ModelConfig):
    """prefill(params, cache, tokens (B,T)) → (last_logits, cache)."""

    def prefill(params, cache, tokens):
        logits, new_cache, _ = forward(params, cfg, {"tokens": tokens},
                                       cache=cache, cache_index=0,
                                       logits_mode="last")
        return logits[:, 0], new_cache

    return prefill


def _working_weights(cfg: ModelConfig, params):
    """``(weights, n)``: the weights ``forward`` computes with, derived once
    from ``params`` by ``compute_weights``, and the bytes of the leaves it
    cast (0 where none needed casting, and ``params`` is returned as it
    is).  Arrays are cast by one jitted program into new buffers, so the
    caller's tree is neither altered nor deleted; shapes
    (``jax.ShapeDtypeStruct``) give the working weights' shapes."""
    cast = partial(compute_weights, cfg=cfg)
    shapes = jax.eval_shape(cast, params)
    n = sum(p.size * jnp.dtype(p.dtype).itemsize for p, w in zip(
        jax.tree.leaves(params), jax.tree.leaves(shapes)) if w.dtype != p.dtype)
    if not n:
        return params, 0
    if any(isinstance(p, jax.ShapeDtypeStruct) for p in jax.tree.leaves(params)):
        return shapes, n
    return jax.jit(cast)(params), n


def _mix32(*words: int) -> int:
    """Fold a tuple of ints into one well-scrambled uint32 stream seed
    (murmur3-finalizer avalanche per word).  Pure Python with explicit
    32-bit masking, so slot indices, steps and prompt hashes of any
    magnitude mix without numpy overflow semantics."""
    h = 0x9E3779B9
    for w in words:
        h = (h ^ (int(w) & 0xFFFFFFFF)) & 0xFFFFFFFF
        h = (h * 0x85EBCA6B) & 0xFFFFFFFF
        h ^= h >> 13
        h = (h * 0xC2B2AE35) & 0xFFFFFFFF
        h ^= h >> 16
    return h


@dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, prompt+generated)
    steps: int


class ServeEngine:
    """Batched greedy/temperature decoding over a fixed slot set.

    It serves from ``weights``: ``params`` with every weight matrix cast
    once, at build, to the compute dtype (``models.model.compute_weights``).
    The caller's ``params`` are neither altered nor deleted.
    """

    def __init__(self, cfg: ModelConfig, params, max_len: int = 256,
                 batch: int = 4, temperature: float = 0.0, seed: int = 0):
        self.cfg = cfg
        self.max_len = max_len
        self.batch = batch
        self.temperature = temperature
        self.seed = seed
        # Serving never updates its weights, so they are cast to the compute
        # dtype once, here, and the programs below cast nothing per call.
        # An engine without params (a placeholder) only checks its inputs.
        self.weights = None
        if params is not None:
            self.weights, n = _working_weights(cfg, params)
            _obs_metrics.set_gauge("serve.weights.cast_at_build", n)
        # Both programs take the cache donated and update it in place.
        self._prefill = jax.jit(make_prefill(cfg), donate_argnums=1)
        self._step = jax.jit(make_serve_step(cfg), donate_argnums=1)

    # -- decoding -----------------------------------------------------------

    def _slot_seeds(self, prompts: np.ndarray) -> list[int]:
        """One PRNG stream seed per slot, decorrelated across
        (engine seed, slot index, prompt content): two engines sharing a
        seed but decoding different prompts draw independent Gumbel
        noise instead of the identical ``seed + step`` sequence."""
        rows = np.ascontiguousarray(prompts, dtype=np.int32)
        return [_mix32(self.seed, slot, zlib.crc32(rows[slot].tobytes()))
                for slot in range(rows.shape[0])]

    def _sample(self, logits: jax.Array, step: int,
                slot_seeds: list[int]) -> jax.Array:
        with _obs_span("serve.sample"):
            if self.temperature <= 0.0:
                return jnp.argmax(logits, axis=-1)
            # Gumbel trick with xoshiro uniforms (the paper's PRNG), one
            # counter stream per (engine, slot, step).
            u = jnp.stack([kops.uniform(_mix32(s, step), logits.shape[-1:])
                           for s in slot_seeds])
            g = -jnp.log(-jnp.log(jnp.maximum(u, 1e-12)))
            return jnp.argmax(logits / self.temperature + g, axis=-1)

    def generate(self, prompts: np.ndarray, n_steps: int) -> GenerationResult:
        """prompts: (B, P) int32; decodes exactly ``n_steps`` tokens.
        ``n_steps=0`` returns the prompt unchanged (no prefill, no
        sampled token).

        Each host step runs in a ``repro.obs`` span (``serve.generate``
        around ``serve.cache_init``, ``serve.prefill``, then per token
        ``serve.sample`` and ``serve.decode_step``, and ``serve.collect``),
        so a profiler trace shows what the host did beside the device."""
        prompts = np.asarray(prompts)
        B, plen = prompts.shape
        if B != self.batch:
            raise ValueError(
                f"prompts batch dimension is {B}, but this engine was "
                f"built with batch={self.batch}; rebuild the engine or "
                f"re-batch the prompts.")
        if n_steps < 0:
            raise ValueError(f"n_steps={n_steps} must be >= 0")
        if plen + n_steps > self.max_len:
            raise ValueError(
                f"prompt length {plen} + n_steps={n_steps} = "
                f"{plen + n_steps} exceeds max_len={self.max_len}; raise "
                f"max_len or decode fewer steps.")
        with _obs_span("serve.generate"):
            toks = jnp.asarray(prompts, jnp.int32)
            if n_steps == 0:
                return GenerationResult(np.asarray(toks), 0)
            slot_seeds = self._slot_seeds(prompts)
            with _obs_span("serve.cache_init"):
                cache = make_cache(self.cfg, B, self.max_len)
            with _obs_span("serve.prefill"):
                logits, cache = self._prefill(self.weights, cache, toks)
            out = [toks]
            for i in range(n_steps):
                tok = self._sample(logits, i, slot_seeds)[:, None]
                out.append(tok)
                if i + 1 < n_steps:
                    with _obs_span("serve.decode_step"):
                        given = cache
                        logits, cache = self._step(self.weights, cache, tok,
                                                   jnp.int32(plen + i))
                    if i == 0:
                        # 0 where a backend or caller keeps the cache
                        # undonated, so every step copies it
                        _obs_metrics.set_gauge("serve.cache.donated", int(all(
                            a.is_deleted() for a in jax.tree.leaves(given))))
            with _obs_span("serve.collect"):
                tokens = np.asarray(jnp.concatenate(out, 1))
        return GenerationResult(tokens, n_steps)
