"""End-to-end behaviour tests for the whole system: the training driver
learns on the synthetic stream, the serving engine decodes coherently, and
the benchmark harness produces every paper table."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import load_config
from repro.launch import compile_cache
from repro.launch import train as train_mod
from repro.models.model import init_params
from repro.serve.engine import ServeEngine
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestTrainSystem:
    def test_short_training_run_improves(self, tmp_path):
        history = train_mod.main([
            "--arch", "olmo-1b", "--variant", "smoke", "--steps", "40",
            "--batch", "8", "--seq", "128", "--lr", "2e-3",
            "--ckpt-dir", str(tmp_path / "ck")])
        losses = [h["loss"] for h in history]
        assert all(np.isfinite(losses))
        # sticky-token stream is learnable: mean of last 10 < first 5
        assert np.mean(losses[-10:]) < np.mean(losses[:5])

    def test_training_is_deterministic(self):
        h1 = train_mod.main(["--arch", "olmo-1b", "--variant", "smoke",
                             "--steps", "5", "--batch", "4", "--seq", "64"])
        h2 = train_mod.main(["--arch", "olmo-1b", "--variant", "smoke",
                             "--steps", "5", "--batch", "4", "--seq", "64"])
        assert [x["loss"] for x in h1] == [x["loss"] for x in h2]


    def test_train_step_donates_state(self):
        """Only one copy of params + optimizer state is live: the step's
        input state is donated to its output."""
        cfg = load_config("olmo-1b", "smoke")
        state = init_train_state(cfg, init_params(cfg, jax.random.PRNGKey(0)))
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
        new, _ = train_mod.jit_train_step(cfg, AdamWConfig())(state, batch)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state))
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(new))

    def test_depth_cut_keeps_widths(self, capsys):
        history = train_mod.main(["--arch", "olmo-1b", "--variant", "smoke",
                                  "--layers", "1", "--steps", "1",
                                  "--batch", "2", "--seq", "16"])
        assert np.isfinite(history[0]["loss"])
        assert "depth cut to 1 of 2 layers" in capsys.readouterr().out


class TestCompileCache:
    def test_default_is_a_fixed_dir_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            compile_cache.enable_compile_cache()
            assert (jax.config.jax_compilation_cache_dir
                    == os.path.join(REPO, ".jax_cache"))
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_dir_is_used_and_nothing_else(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, train.main sets nothing and
        its compiles land in that directory."""
        script = (
            "import jax\n"
            "from repro.launch import train\n"
            "train.main(['--variant', 'smoke', '--steps', '1', '--batch', "
            "'2', '--seq', '16'])\n"
            "print('DIR', jax.config.jax_compilation_cache_dir)\n")
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        r = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stderr
        assert f"DIR {tmp_path}" in r.stdout
        assert any(tmp_path.iterdir())


class TestServeSystem:
    def test_generation_runs_and_is_deterministic_greedy(self):
        cfg = load_config("gemma-2b", "smoke")
        params = init_params(cfg, jax.random.PRNGKey(1))
        engine = ServeEngine(cfg, params, max_len=48, batch=2,
                             temperature=0.0)
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 8)).astype(np.int32)
        r1 = engine.generate(prompts, 16)
        r2 = engine.generate(prompts, 16)
        np.testing.assert_array_equal(r1.tokens, r2.tokens)
        assert r1.tokens.shape == (2, 8 + 16)
        assert (r1.tokens >= 0).all() and (r1.tokens < cfg.vocab_size).all()

    def test_sampled_generation_differs_by_seed(self):
        cfg = load_config("olmo-1b", "smoke")
        params = init_params(cfg, jax.random.PRNGKey(1))
        prompts = np.zeros((2, 4), np.int32)
        a = ServeEngine(cfg, params, max_len=40, batch=2, temperature=1.0,
                        seed=1).generate(prompts, 16)
        b = ServeEngine(cfg, params, max_len=40, batch=2, temperature=1.0,
                        seed=2).generate(prompts, 16)
        assert (a.tokens != b.tokens).any()


class TestBenchmarkHarness:
    def test_table1_all_rows_match_paper(self):
        from benchmarks import table1
        rows = table1.generate_rows()
        assert len(rows) == 6
        assert all(r["match"] for r in rows)

    def test_fig2_aggregates_within_bands(self):
        from benchmarks import fig2
        rows, agg = fig2.generate()
        assert len(rows) == 6
        assert abs(agg["geomean_speedup"] - 1.47) < 0.07
        assert abs(agg["peak_ipc"] - 1.75) < 0.09

    def test_fig3_structure(self):
        from benchmarks import fig3
        data = fig3.generate()
        assert data["markers"] and data["peaks"]
        assert 1.0 < data["steady"] < 2.0
