"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + no NaNs, per the assignment spec — plus decode
path equivalence (prefill+decode == full forward) for the causal archs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, applicable_shapes, load_config
from repro.models.model import forward, init_params, loss_fn
from repro.models.transformer import layer_plan
from repro.serve.engine import (ServeEngine, make_cache, make_prefill,
                                make_serve_step)
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, T=32):
    if cfg.frontend == "audio":
        return {"embeds": jax.random.normal(KEY, (B, T, cfg.d_model),
                                            jnp.float32),
                "labels": jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)}
    return {"tokens": jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)}


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    cfg = load_config(request.param, "smoke")
    params = init_params(cfg, KEY)
    return request.param, cfg, params


class TestSmoke:
    def test_forward_shapes_and_finite(self, arch_setup):
        name, cfg, params = arch_setup
        batch = _batch(cfg)
        logits, _, aux = forward(params, cfg, batch)
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert not bool(jnp.isnan(logits).any())
        assert not bool(jnp.isnan(aux))

    def test_one_train_step(self, arch_setup):
        name, cfg, params = arch_setup
        step = jax.jit(make_train_step(cfg, AdamWConfig(total_steps=10)))
        state = init_train_state(cfg, params)
        state, metrics = step(state, _batch(cfg))
        assert np.isfinite(metrics["loss"])
        assert np.isfinite(metrics["grad_norm"]) and metrics["grad_norm"] > 0
        # params actually moved
        delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                    for a, b in zip(jax.tree.leaves(state["params"]),
                                    jax.tree.leaves(params)))
        assert delta > 0

    def test_initial_loss_near_uniform(self, arch_setup):
        name, cfg, params = arch_setup
        loss, m = loss_fn(params, cfg, _batch(cfg))
        assert float(m["nll"]) == pytest.approx(np.log(cfg.vocab_size),
                                                abs=2.0)

    def test_microbatched_grads_match(self, arch_setup):
        """Gradient accumulation must be loss-equivalent to the full batch."""
        name, cfg, params = arch_setup
        if cfg.moe is not None:
            pytest.skip("MoE routing is capacity-per-group: microbatching "
                        "legitimately changes dispatch")
        batch = _batch(cfg, B=4)
        s1 = jax.jit(make_train_step(cfg, AdamWConfig()))(
            init_train_state(cfg, params), batch)[1]
        s2 = jax.jit(make_train_step(cfg, AdamWConfig(), n_microbatches=2))(
            init_train_state(cfg, params), batch)[1]
        assert float(s1["loss"]) == pytest.approx(float(s2["loss"]), rel=1e-3)


class TestDecode:
    def test_prefill_plus_decode_matches_forward(self, arch_setup):
        """Teacher-forced decode must reproduce the full-sequence forward —
        this exercises KV-cache indexing AND the recurrent states of
        mamba/rwkv in one assertion."""
        name, cfg, params = arch_setup
        if cfg.is_encoder_only:
            pytest.skip("encoder-only: no decode step")
        B, T = 2, 24
        tokens = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
        full, _, _ = forward(params, cfg, {"tokens": tokens})

        plen = 8
        cache = make_cache(cfg, B, T)
        prefill = jax.jit(make_prefill(cfg))
        step = jax.jit(make_serve_step(cfg))
        logits_p, cache = prefill(params, cache, tokens[:, :plen])
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(full[:, plen - 1]),
                                   rtol=2e-2, atol=2e-2)
        for t in range(plen, T):
            logits_t, cache = step(params, cache, tokens[:, t:t + 1],
                                   jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(logits_t), np.asarray(full[:, t]),
                rtol=2e-2, atol=2e-2,
                err_msg=f"{name} decode diverges at t={t}")


    def test_donated_in_place_steps_match_undonated(self, arch_setup):
        """A prefill and 3 steps through the engine's donated programs,
        which write each call's update into the cache in place, give the
        logits and caches of undonated jits bit for bit; each call leaves
        every attention cache slot but the positions it writes as it was
        (the slots start as junk, which the masks hide)."""
        name, cfg, params = arch_setup
        if cfg.is_encoder_only:
            pytest.skip("encoder-only: no decode step")
        B, plen, S = 2, 8, 16
        tokens = jax.random.randint(KEY, (B, plen + 3), 0, cfg.vocab_size)
        eng = ServeEngine(cfg, params, max_len=S, batch=B)
        prefill = jax.jit(make_prefill(cfg))
        step = jax.jit(make_serve_step(cfg))
        junk = jax.tree_util.tree_map_with_path(
            lambda path, a: (jax.random.normal(KEY, a.shape, a.dtype)
                             if path[-1].key in ("k", "v") else a),
            make_cache(cfg, B, S))

        def same(a, b):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                            strict=True):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

        def call(program, ref_program, ref_cache, cache, toks, *index):
            # a device copy: a host view of a buffer keeps it undonated
            before = jax.tree.map(jnp.copy, cache)
            ref = ref_program(params, ref_cache, toks, *index)
            got = program(params, cache, toks, *index)
            same(got, ref)
            assert all(a.is_deleted() for a in jax.tree.leaves(cache))
            start = int(index[0]) if index else 0
            written = np.arange(start, start + toks.shape[1])
            kv = [(old, new) for (path, old), new in zip(
                jax.tree.leaves_with_path(before), jax.tree.leaves(got[1]),
                strict=True) if path[-1].key in ("k", "v")]
            assert kv or cfg.ssm is not None, name
            for old, new in kv:       # (..., B, S, Hkv, Dh): S is axis -3
                np.testing.assert_array_equal(
                    np.delete(np.asarray(old), written, axis=-3),
                    np.delete(np.asarray(new), written, axis=-3))
            return ref[1], got[1]

        ref_cache, cache = call(eng._prefill, prefill, junk,
                                jax.tree.map(jnp.copy, junk), tokens[:, :plen])
        for t in range(plen, plen + 3):
            ref_cache, cache = call(eng._step, step, ref_cache, cache,
                                    tokens[:, t:t + 1], jnp.int32(t))


class TestLayerPlan:
    def test_every_arch_has_scan_structure(self):
        for arch in ARCHS:
            cfg = load_config(arch, "full")
            prefix, period, n_periods = layer_plan(cfg)
            assert len(prefix) + len(period) * n_periods == cfg.n_layers
            assert n_periods >= 1, arch

    def test_jamba_period(self):
        cfg = load_config("jamba-v0.1-52b", "full")
        prefix, period, n_periods = layer_plan(cfg)
        assert len(prefix) == 0 and len(period) == 8 and n_periods == 4
        assert [s.mixer for s in period] == list("mmmmammm")
        assert [s.is_moe for s in period] == [False, True] * 4

    def test_deepseek_dense_first(self):
        cfg = load_config("deepseek-moe-16b", "full")
        prefix, period, n_periods = layer_plan(cfg)
        assert len(prefix) == 1 and not prefix[0].is_moe
        assert n_periods == 27 and period[0].is_moe

    def test_applicable_shapes_per_design(self):
        """DESIGN.md §5 skip table."""
        shapes = {a: applicable_shapes(load_config(a, "full")) for a in ARCHS}
        assert "long_500k" in shapes["rwkv6-1.6b"]
        assert "long_500k" in shapes["jamba-v0.1-52b"]
        assert "long_500k" not in shapes["olmo-1b"]
        assert "decode_32k" not in shapes["hubert-xlarge"]
        assert "long_500k" not in shapes["hubert-xlarge"]
        total = sum(len(v) for v in shapes.values())
        assert total == 31          # 40 − 8 long skips − 1 hubert decode
