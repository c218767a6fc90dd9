"""ShapeDtypeStruct stand-ins for every model input — the dry-run's
weak-type-correct, shardable, zero-allocation input builders.

``input_specs(cfg, shape)`` returns the (kw)args the lowered step function
takes: for training that's {state, batch}; for decode {params, cache,
tokens, cache_index}.  Everything is built with ``jax.eval_shape`` over the
real init functions, so specs can never drift from the code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.model import forward, init_params
from repro.models.transformer import init_stack_cache
from repro.parallel.autoshard import activation_sharding
from repro.parallel.sharding import ShardingRules
from repro.serve.engine import make_serve_step
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def params_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def train_state_specs(cfg: ModelConfig):
    params = params_specs(cfg)
    return jax.eval_shape(lambda p: init_train_state(cfg, p), params)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    B, T = shape.global_batch, shape.seq_len
    if cfg.frontend == "audio":
        return {"embeds": sds((B, T, cfg.d_model), cfg.dtype),
                "labels": sds((B, T), jnp.int32)}
    return {"tokens": sds((B, T), jnp.int32)}


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    return jax.eval_shape(
        lambda: init_stack_cache(cfg, shape.global_batch, shape.seq_len))


def decode_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    return {"params": params_specs(cfg),
            "cache": cache_specs(cfg, shape),
            "tokens": sds((shape.global_batch, 1), jnp.int32),
            "cache_index": sds((), jnp.int32)}


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """The full argument spec set for the cell's step function."""
    if shape.kind == "decode":
        return decode_specs(cfg, shape)
    return {"state": train_state_specs(cfg), "batch": batch_specs(cfg, shape)}


def step_and_specs(cfg: ModelConfig, shape: ShapeConfig,
                    rules: ShardingRules, mesh):
    """The cell's step under ``rules``: (fn, args tuple of
    ShapeDtypeStructs, in_shardings tuple).  ``fn`` runs inside an
    ``activation_sharding`` context over ``mesh``."""
    ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    b_axis, t_axis = rules.batch_spec(shape)
    seq_sharded = b_axis is None and t_axis is not None

    def with_ctx(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with activation_sharding(
                    mesh, dp=rules.dp_axes,
                    tp="model" if rules.use_tp else None,
                    seq_sharded=seq_sharded):
                return fn(*a, **kw)
        return wrapped

    if shape.kind == "decode":
        sp = decode_specs(cfg, shape)
        step = with_ctx(make_serve_step(cfg))
        in_sh = (ns(rules.params_pspecs(sp["params"])),
                 ns(rules.cache_pspecs(sp["cache"], shape)),
                 NamedSharding(mesh, rules.batch_spec(shape)
                               if shape.global_batch > 1 else P(None, None)),
                 NamedSharding(mesh, P()))
        args = (sp["params"], sp["cache"], sp["tokens"], sp["cache_index"])
        return step, args, in_sh

    if shape.kind == "prefill":
        sp = {"params": params_specs(cfg),
              "batch": batch_specs(cfg, shape)}

        def prefill_step(params, batch):
            logits, _, _ = forward(params, cfg, batch, logits_mode="last")
            return logits[:, 0]

        in_sh = (ns(rules.params_pspecs(sp["params"])),
                 jax.tree.map(lambda _: NamedSharding(
                     mesh, rules.batch_spec(shape)), sp["batch"]))
        return with_ctx(prefill_step), (sp["params"], sp["batch"]), in_sh

    # train
    sp = input_specs(cfg, shape)
    opt_cfg = AdamWConfig()
    step = with_ctx(make_train_step(cfg, opt_cfg))
    state_pspecs = {
        "params": rules.params_pspecs(sp["state"]["params"]),
        "opt": {"m": rules.params_pspecs(sp["state"]["opt"]["m"]),
                "v": rules.params_pspecs(sp["state"]["opt"]["v"]),
                "step": P()},
    }
    bspec = rules.batch_spec(shape)

    def batch_sh(leaf):
        nd = len(leaf.shape)
        spec = bspec if nd == 2 else P(*(tuple(bspec) + (None,) * (nd - 2)))
        return NamedSharding(mesh, spec)

    in_sh = (ns(state_pspecs), jax.tree.map(batch_sh, sp["batch"]))
    return step, (sp["state"], sp["batch"]), in_sh
