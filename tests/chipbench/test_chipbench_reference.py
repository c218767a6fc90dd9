"""The plain reference against ``repro.models`` at a small size on the
CPU, with the program run at float32 so that the two agree to rounding:
forward logits, the loss, its gradients, one AdamW step, and greedy
serving through the engine's cache."""

import ast
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import system, weights
from chipbench.adapters import dense_lm as adapter
from chipbench.drivers import closed_batches
from chipbench.reference import adamw, dense_lm as ref
from chipbench_cells import REPO, TINY
from repro.models.model import forward, loss_fn
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state

CONFIGS = {name: dict(json.loads((REPO / f"chipbench/configs/{name}.json").read_text()),
                      **TINY, compute_dtype="float32")
           for name in ("olmo-1b", "phi3-mini-3.8b-4l")}


@pytest.fixture(params=sorted(CONFIGS))
def case(request):
    c = CONFIGS[request.param]
    cfg = system.program_config(c)
    w = weights.make(ref.layout(c), 2**32 + 17)
    return c, cfg, w, system.program_params(cfg, c, w)


def tokens(c, b=2, t=12, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, c["vocab_size"], (b, t)), jnp.int32)


def test_reference_imports_nothing_of_the_program():
    for path in (REPO / "chipbench" / "reference").glob("*.py"):
        tree = ast.parse(path.read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
        names += [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
        assert not any(n.startswith(("repro", "chipbench.system")) for n in names), path


def test_forward_logits(case):
    c, cfg, w, params = case
    toks = tokens(c)
    got, _, _ = forward(params, cfg, {"tokens": toks})
    want = ref.logits(w, c, ref.hidden(w, c, toks))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_loss_and_gradients(case):
    c, cfg, w, params = case
    toks = tokens(c, t=16)
    (got, _), g_prog = jax.value_and_grad(lambda p: loss_fn(p, cfg, {"tokens": toks}),
                                          has_aux=True)(params)
    want, g_ref = jax.value_and_grad(lambda p: ref.loss(p, c, toks, 1e-4))(w)
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    g_prog = adapter.from_program(g_prog)
    assert sorted(g_prog) == sorted(g_ref)
    for k in g_ref:
        np.testing.assert_allclose(g_prog[k], g_ref[k], atol=1e-5, rtol=1e-3, err_msg=k)


def test_one_adamw_step(case):
    c, cfg, w, params = case
    o = dict(lr=1e-2, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
             grad_clip=1.0, warmup_steps=2, total_steps=10, min_lr_ratio=0.1)
    g = jax.tree.map(lambda p: jnp.full_like(p, 0.5) * jnp.sign(p), params)
    new, _, _ = adamw_update(AdamWConfig(**o), params, g, init_opt_state(params))
    gw = adamw.clip(adapter.from_program(g), o["grad_clip"])
    want, _ = adamw.update(o, w, gw, adamw.init(w), 1, adamw.lr(o, 1), ref.decayed)
    got = adapter.from_program(new)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=1e-6, rtol=1e-5, err_msg=k)


def test_greedy_serving_matches(case):
    c, cfg, w, params = case
    t = {"batch": 2, "prompt_len": 6, "max_len": 16, "temperature": 0.0}
    engine = system.serve_engine(cfg, params, t, seed=5)
    p = np.asarray(tokens(c, 2, 6, seed=1))
    out = engine.generate(p, 5).tokens
    gap = closed_batches.served_gap(ref, c, w, jnp.asarray(out), 6)
    assert float(gap) < 1e-4
