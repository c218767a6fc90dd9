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

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.model import forward
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

    ``autotune=True`` flips a process-wide kernel-config default (see
    ``__init__``); use the engine as a context manager or call
    :meth:`close` to restore it.
    """

    def __init__(self, cfg: ModelConfig, params, max_len: int = 256,
                 batch: int = 4, temperature: float = 0.0, seed: int = 0,
                 autotune: bool = False, power_cap_mw: float | None = None,
                 persist_tuned_defaults: bool = False, system=None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.batch = batch
        self.temperature = temperature
        self.seed = seed
        self.autotune = autotune
        self.power_cap_mw = power_cap_mw
        self.system = system
        self.operating_plan = None
        self.system_plan = None
        self._prev_tuned: bool | None = None
        self._persist_tuned = persist_tuned_defaults
        self._closed = False
        if power_cap_mw is not None and not autotune:
            raise ValueError(
                f"power_cap_mw={power_cap_mw} only constrains the autotuned "
                f"operating plan, but autotune=False, so the cap would be "
                f"silently ignored. Either pass autotune=True so the engine "
                f"searches an operating plan under the cap, or drop "
                f"power_cap_mw to run with the static kernel defaults.")
        if autotune:
            # Engine setup is where tuning pays: the softmax/PRNG kernels
            # run every decode step, so let the facade's tuner pick their
            # tiling once (cached) before the jit traces below bake it in.
            # The context-scoped ``repro.api.config`` would not outlive
            # __init__, while the traces resolve tilings lazily at the
            # first generate() — so this uses the persistent setter and
            # records the value it displaced; ``close()`` (or exiting the
            # engine's ``with`` block) restores it, unless the caller
            # opted out via ``persist_tuned_defaults=True``.
            from repro import api
            self._prev_tuned = kops.set_tuned_defaults(True)
            # Also pick the cluster operating plan for the decode-hot
            # kernels: the heterogeneous (DVFS-island) search with
            # per-island block refinement, which never scores worse than
            # the homogeneous ladder under the same power cap.  The whole
            # search runs on the batched cost oracle over the repro.perf
            # timing memo (tune.cost.evaluate_batch), so engine startup
            # prices the full island x strategy x block space in well
            # under a second instead of re-simulating per candidate.
            # Advisory on this backend — `operating_plan` is what a
            # Snitch-cluster deployment of the engine would pin.
            tuner = api.Tuner(api.Target.homogeneous(
                power_cap_mw=power_cap_mw))
            with _obs_span("serve.autotune", power_cap_mw=power_cap_mw):
                self.operating_plan = {
                    name: tuner.operating_point(name, heterogeneous=True,
                                                per_island_blocks=True)
                    for name in ("softmax", "prng")}
                if system is not None:
                    # Manycore deployment: also size the part — cluster
                    # count x DVFS point under the same (system) power
                    # cap, priced through repro.system.  ``system`` here
                    # is a SystemConfig whose cluster count is the upper
                    # bound of the search.
                    sys_tuner = api.Tuner(api.Target.system(
                        system, power_cap_mw=power_cap_mw))
                    self.system_plan = {
                        name: sys_tuner.operating_point(
                            name, n_clusters=system.n_clusters)
                        for name in ("softmax", "prng")}
        # Both programs take the cache donated and update it in place.
        self._prefill = jax.jit(make_prefill(cfg), donate_argnums=1)
        self._step = jax.jit(make_serve_step(cfg), donate_argnums=1)

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Undo the engine's process-wide side effect.

        ``autotune=True`` enables tuned kernel defaults through the
        persistent setter (the jit traces resolve tilings lazily,
        possibly on another thread, so a scoped override cannot cover
        them); ``close()`` restores whatever value that setter displaced,
        so building an autotuned engine no longer flips the default for
        every later caller in the process.  Idempotent.  The escape
        hatch ``persist_tuned_defaults=True`` keeps the enablement alive
        past ``close()`` — for setups that deliberately build one
        throwaway engine to warm the process-wide tuned state.
        """
        if self._closed:
            return
        self._closed = True
        if self._prev_tuned is not None and not self._persist_tuned:
            kops.set_tuned_defaults(self._prev_tuned)

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

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
                logits, cache = self._prefill(self.params, cache, toks)
            out = [toks]
            for i in range(n_steps):
                tok = self._sample(logits, i, slot_seeds)[:, None]
                out.append(tok)
                if i + 1 < n_steps:
                    with _obs_span("serve.decode_step"):
                        given = cache
                        logits, cache = self._step(self.params, cache, tok,
                                                   jnp.int32(plen + i))
                    if i == 0:
                        # 0 where a backend or caller keeps the cache
                        # undonated, so every step copies it
                        _obs_metrics.set_gauge("serve.cache.donated", int(all(
                            a.is_deleted() for a in jax.tree.leaves(given))))
            with _obs_span("serve.collect"):
                tokens = np.asarray(jnp.concatenate(out, 1))
        return GenerationResult(tokens, n_steps)
