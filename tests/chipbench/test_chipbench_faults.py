"""A whole run of a tiny cell on the CPU (the look for a chip skipped),
sound and with the timed path broken underneath: ``correct`` must come
out true for the sound run and false for each fault the cell can have."""

import time

import jax
import pytest

from chipbench import harness, system

SEED = 3_000_000_019


def run(root, name):
    cell = harness.load_cell(name, root)
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(), platform="cpu")


def test_sound_runs_are_correct(root):
    for name in ("tiny.serve", "tiny.train"):
        r = run(root, name)
        assert r["correct"], r
        assert r["attempted"] > 0 and r["failed"] == 0
        assert list(r)[-1] == "checks"
    assert set(run(root, "tiny.serve")["checks"]) == {"logit_gap", "sample_z"}


def test_altered_token_is_caught(root, monkeypatch):
    """One served token per row changed where the engine produces it."""
    from repro.serve.engine import ServeEngine

    real = ServeEngine.generate

    def altered(self, prompts, n_steps):
        out = real(self, prompts, n_steps)
        tokens = out.tokens.copy()
        tokens[:, -1] = (tokens[:, -1] + 1) % self.cfg.vocab_size
        return type(out)(tokens, out.steps)

    monkeypatch.setattr(ServeEngine, "generate", altered)
    r = run(root, "tiny.serve")
    assert not r["correct"], r


@pytest.mark.parametrize("fault", ["greedy_sampled", "constant_uniform"])
def test_broken_sampling_is_caught(root, monkeypatch, fault):
    """The sampled requests served greedily, or every uniform of the
    sampling draw reading 0.5, where the engine samples."""
    from repro.kernels import ops as kops
    from repro.serve.engine import ServeEngine

    if fault == "greedy_sampled":
        monkeypatch.setattr(ServeEngine, "_sample",
                            lambda self, logits, step, seeds: jax.numpy.argmax(logits, -1))
    else:
        monkeypatch.setattr(kops, "uniform",
                            lambda seed, shape, *a, **k: jax.numpy.full(shape, 0.5))
    r = run(root, "tiny.serve")
    assert r["checks"]["sample_z"]["value"] > r["checks"]["sample_z"]["limit"], r
    assert not r["correct"], r


def _faulty_step(monkeypatch, fault):
    real = system.train_step

    def make(cfg, t):
        step = jax.jit(real(cfg, t).__wrapped__)
        if fault == "unchanged":
            return lambda state, batch: (state, step(state, batch)[1])
        half = t["batch"] // 2
        return lambda state, batch: step(state, {"tokens": batch["tokens"][:half]})

    monkeypatch.setattr(system, "train_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step_is_caught(root, monkeypatch, fault):
    _faulty_step(monkeypatch, fault)
    r = run(root, "tiny.train")
    assert not r["correct"], r
