"""The model's named scopes reach the HLO of every program the serving and
training paths compile, so a device trace can split device time by model
part: the decode step, the prefill and the train step of a dense model,
and the forward of a hybrid with SSM and MoE layers.  Backward ops carry
the forward's scope inside JAX's ``jvp``/``transpose`` wrappers."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import load_config
from repro.models.model import forward, init_params
from repro.serve.engine import make_cache, make_prefill, make_serve_step
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step

DENSE = ("weight_cast", "embed", "layer_scan", "norm", "attention", "ffn", "readout")
_LOC = re.compile(r'loc\("([^"]*)"')


def _scopes(lowered) -> set:
    """Every '/'-separated name in the op names of the lowered program,
    with transformation wrappers (``transpose(jvp(x))``) stripped."""
    names = set()
    for loc in _LOC.findall(lowered.as_text(debug_info=True)):
        for part in loc.split("/"):
            while (m := re.fullmatch(r"[\w.-]*\((.*)\)", part)):
                part = m.group(1)
            names.add(part)
    return names


@pytest.fixture(scope="module")
def dense():
    # bf16 compute on fp32 weights, as served and trained on the chip, so
    # that the weight cast is in the programs
    cfg = load_config("olmo-1b", "smoke").replace(dtype="bfloat16")
    return cfg, jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _lower(dense, program):
    cfg, params = dense
    cache = jax.eval_shape(lambda: make_cache(cfg, 2, 32))
    if program == "serve_step":
        tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        return jax.jit(make_serve_step(cfg)).lower(params, cache, tok, jnp.int32(5))
    if program == "prefill":
        toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
        return jax.jit(make_prefill(cfg)).lower(params, cache, toks)
    state = jax.eval_shape(lambda p: init_train_state(cfg, p), params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    return jax.jit(make_train_step(cfg, AdamWConfig())).lower(state, batch)


@pytest.mark.parametrize("program,want", [
    ("serve_step", DENSE), ("prefill", DENSE),
    ("train_step", DENSE + ("loss", "optimizer"))])
def test_dense_programs_carry_scopes(dense, program, want):
    got = _scopes(_lower(dense, program))
    assert set(want) <= got, sorted(set(want) - got)
    if program != "train_step":
        assert not {"loss", "optimizer"} & got


@pytest.mark.parametrize("program", ["serve_step", "prefill"])
def test_engine_programs_keep_scan_and_attention_scopes(dense, program):
    """The engine's programs, with the cache donated and carried through
    the layer scan, keep ``layer_scan`` and ``attention`` in the op_name
    metadata that a trace carries, the in-place cache writes under
    ``attention``."""
    from repro.serve.engine import ServeEngine
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=32, batch=2)
    cache = jax.eval_shape(lambda: make_cache(cfg, 2, 32))
    if program == "serve_step":
        tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        lowered = eng._step.lower(params, cache, tok, jnp.int32(5))
    else:
        toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
        lowered = eng._prefill.lower(params, cache, toks)
    text = lowered.compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/layer_scan/" in n and "/attention/" in n for n in names)
    assert any(re.search(r"/layer_scan/.*/attention/dynamic_update_slice", n)
               for n in names)


@pytest.mark.parametrize("program", ["serve_step", "prefill"])
def test_engine_programs_cast_no_weights(dense, program):
    """Lowered on the engine's working weights, cast once when it was
    built, the serve step and the prefill hold no op under
    ``weight_cast``; on the fp32 params they do
    (``test_dense_programs_carry_scopes``)."""
    from repro.serve.engine import ServeEngine
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=32, batch=2)
    assert "weight_cast" in _scopes(_lower(dense, program))
    got = _scopes(_lower((cfg, eng.weights), program))
    assert "weight_cast" not in got
    assert set(DENSE) - {"weight_cast"} <= got


def test_backward_carries_forward_scopes(dense):
    """In the compiled train step, the op_name metadata that a trace
    carries puts the backward's ops under the forward's scopes."""
    text = _lower(dense, "train_step").compile().as_text()
    for scope in ("attention", "ffn", "norm"):
        assert re.search(rf'op_name="[^"]*transpose\(jvp\(layer_scan\)\)/[^"]*/{scope}/', text)


def test_hybrid_mixers_carry_scopes():
    cfg = load_config("jamba-v0.1-52b", "smoke")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    lowered = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t})[0]).lower(params, toks)
    assert {"layer_scan", "norm", "attention", "ssm", "moe", "ffn"} <= _scopes(lowered)
