"""The Pallas kernels compile for a TPU v5e at the shapes the model path
calls them with.  Nothing runs: each kernel is compiled by the TPU compiler
against a described (not attached) v5e:2x2, which refuses what interpret
mode accepts — misaligned blocks, casts and gathers Mosaic cannot lower,
blocks over the VMEM budget.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import expf, logf, montecarlo, ops, prng, softmax_tpu

VOCAB = 50304                 # olmo-1b
B, H, T, S = 8, 16, 512, 1024  # prefill: batch, heads, prompt, max_len


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, shape, dtype):
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows, cols", [(B * H * T, S), (8, 65536)],
                         ids=["prefill_scores", "64k_columns"])
def test_softmax_compiles(one_chip, rows, cols):
    _compile(one_chip, lambda x: softmax_tpu.softmax_2d(x, block_rows=8),
             (rows, cols), jnp.float32)


def test_softmax_grad_compiles(one_chip):
    def loss(x):
        return jnp.sum(softmax_tpu.softmax_2d(x, block_rows=8) ** 2)
    _compile(one_chip, jax.grad(loss), (B * H, S), jnp.float32)


@pytest.mark.parametrize("kind", ["xoshiro128p", "lcg"])
def test_uniform_compiles_vocab_wide(one_chip, kind):
    br = prng.DEFAULT_BLOCK_ROWS
    rows = -(-VOCAB // (br * prng.LANES)) * br     # as ops.uniform pads
    _compile(one_chip, lambda s: prng.uniform_2d(s, kind=kind, block_rows=br,
                                                 shape=(rows, prng.LANES)),
             (), jnp.uint32)


@pytest.mark.parametrize("kind", ["xoshiro128p", "lcg"])
@pytest.mark.parametrize("problem", ["pi", "poly"])
def test_montecarlo_compiles(one_chip, kind, problem):
    _compile(one_chip, lambda s: montecarlo.mc_partial_sums(
        s, kind=kind, problem=problem, iters=16, n_blocks=8), (), jnp.uint32)


def test_log_compiles(one_chip):
    _compile(one_chip, lambda x: logf.log_2d(x), (4096, logf.LANES),
             jnp.float32)


def test_exp_compiles(one_chip):
    _compile(one_chip, lambda x: expf.exp_2d(x), (4096, expf.LANES),
             jnp.float32)


@pytest.mark.parametrize("cols", [65536 * 2, 65536 * 8])
def test_softmax_rows_over_vmem_limit_raise(cols):
    x = jax.ShapeDtypeStruct((4, cols), jnp.float32)
    with pytest.raises(ValueError, match=str(softmax_tpu.MAX_BLOCK_ELEMS)):
        jax.eval_shape(lambda a: ops.softmax(a, impl="pallas"), x)


def test_softmax_at_vmem_limit_is_accepted():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 65536)),
                    jnp.float32)
    y = ops.softmax(x, impl="pallas")
    np.testing.assert_allclose(np.asarray(y).sum(-1), 1.0, rtol=1e-5)


def _top_level_ops(text: str, dims: str) -> list[tuple[str, str]]:
    """(instruction, opcode) of every op outside fusion bodies whose
    result has shape ``[dims]``."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    ops, body = [], None
    for line in text.splitlines():
        if not line.startswith(" ") and line.endswith("{"):
            body = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = re.match(rf"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[{dims}\]\S* "
                     r"([\w\-]+)\(", line)
        if m and body not in fused:
            ops.append(m.groups())
    return ops


def test_serve_step_updates_cache_in_place(one_chip, monkeypatch):
    """The engine's decode step, compiled for a v5e at OLMo-1B's widths
    (2 of 16 layers, batch 8, 2048 slots) with the Pallas softmax, moves
    no whole layer or stack of the KV cache: the only cache-sized ops are
    the two in-place writes of the new token's K and V.  Left to choose
    the carried cache's layout, the compiler would relay out each stack on
    entry and exit."""
    from repro.configs import load_config
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine, make_cache

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = load_config("olmo-1b").replace(
        n_layers=2, layer_types="aa", dtype="bfloat16", softmax_impl="pallas")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: make_cache(cfg, 8, 2048)))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    index = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    eng = ServeEngine(cfg, params, max_len=2048, batch=8)
    text = eng._step.lower(params, cache, tok, index).compile().as_text()
    assert "tpu_custom_call" in text
    layer = "8,2048,16,128"
    assert not _top_level_ops(text, layer)
    stack = [op for op in _top_level_ops(text, "2," + layer)
             if op[1] not in ("parameter", "get-tuple-element", "while")]
    assert len(stack) == 2 and all(
        op == "fusion" and "dynamic-update-slice" in name
        for name, op in stack), stack
