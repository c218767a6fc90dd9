"""The system under test, as the benchmark drives it.

This is the one module of the benchmark that imports the program
(``src/repro``).  It builds the program's model configuration from a
configuration file, hands it the benchmark's weights, and returns the
entry points the windows drive: ``ServeEngine.generate`` and the jitted,
donated training step.  It also plants the one fault of the program that
the calibration and the tests need in place (``constant_uniforms``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import sys
import typing
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import load_config  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.train import jit_train_step  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.train.optimizer import AdamWConfig  # noqa: E402
from repro.train.train_step import init_train_state  # noqa: E402

_NORMS = {"nonparametric_layernorm": "nonparam_ln", "rmsnorm": "rmsnorm"}

#: ``ModelConfig`` sizes and precisions, set from the file's keys.
_SIZES = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
          "d_head": "head_dim", "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "max_seq_len": "max_position_embeddings", "dtype": "compute_dtype",
          "param_dtype": "param_dtype"}
#: ``ModelConfig`` fields stated by keys of the file (``norm`` in the
#: reference's names, ``hidden_act`` in the program's).
_KEYS = {"norm": ("norm", _NORMS.get), "tie_embeddings": ("tie_word_embeddings", None),
         "act": ("hidden_act", None), "rope_theta": ("rope_theta", None)}


def adapter(c: dict):
    return importlib.import_module(f"chipbench.adapters.{c['reference']}")


def architecture_fields(cls) -> list:
    """The fields of the config class ``cls`` that say what the model
    computes: all but its name and family, the sizes set from the file's
    keys, and the "execution knobs" block of ``ModelConfig``, ``dtype``
    through ``vocab_parallel_ce`` (how the program runs the model).  A
    field added anywhere else counts as architecture."""
    names = [f.name for f in dataclasses.fields(cls)]
    knobs = names[names.index("dtype"):names.index("vocab_parallel_ce") + 1]
    return [f for f in names if f not in knobs and f not in _SIZES
            and f not in ("name", "family")]


def _stated(c: dict) -> dict:
    """The config fields that the file's own keys state."""
    return {f: (to(c[k]) if to else c[k]) for f, (k, to) in _KEYS.items() if k in c}


def _program(c: dict, cls) -> dict:
    """The file's ``program`` object as field values of the config class
    ``cls``: an object becomes the dataclass its field holds
    (``MoEConfig``, ``SSMConfig``), a list a tuple.  Raises on a name
    that the file's own keys already set, and on any other name that is
    not an architecture field of ``cls`` (a knob such as
    ``softmax_impl``, the name, or no field at all)."""
    hints = typing.get_type_hints(cls)
    arch = architecture_fields(cls)
    out = {}
    for name, value in c.get("program", {}).items():
        if name in _SIZES or name in _stated(c):
            raise ValueError(f"program field {name!r} is set by the file's own keys")
        if name not in arch:
            raise ValueError(f"program field {name!r} is not an architecture field "
                             f"of {cls.__name__}")
        if isinstance(value, dict):
            kinds = [t for t in typing.get_args(hints[name]) or (hints[name],)
                     if dataclasses.is_dataclass(t)]
            if not kinds:
                raise ValueError(f"program field {name!r} takes no object")
            try:
                value = kinds[0](**value)
            except TypeError as e:
                raise ValueError(f"program field {name!r}: {e}") from None
        elif isinstance(value, list):
            value = tuple(value)
        out[name] = value
    return out


def program_config(c: dict):
    """The registry's architecture ``c['arch']``, cut to the file's depth
    (``with_depth``, which keeps the registry's layer pattern), at the
    sizes and precisions the file states.  Raises where an architecture
    field of the registry's config differs from the file: from what the
    file's keys (norm, tied head, activation, rotary base) or its
    optional ``program`` object (architecture fields by name) state, and,
    for a field the file does not state, from the field's default (a
    dense all-attention model)."""
    base = load_config(c["arch"])
    cls = type(base)
    sizes = {f: c[k] for f, k in _SIZES.items()}
    program = _program(c, cls)
    cfg = base.with_depth(sizes["n_layers"]).replace(**sizes)
    want = cls(name=base.name, family=base.family, **sizes).replace(**_stated(c), **program)
    wrong = {f: (getattr(cfg, f), getattr(want, f)) for f in architecture_fields(cls)
             if getattr(cfg, f) != getattr(want, f)}
    if wrong:
        raise ValueError(f"{c['arch']} departs from its configuration file "
                         f"(program, file): {wrong}")
    return cfg


def program_params(cfg, c: dict, weights: dict):
    """The benchmark's weights in the program's parameter tree; raises if
    the tree or a shape differs from what ``init_params`` builds."""
    tree = adapter(c).to_program(weights, c)
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    got_s, want_s = jax.tree.structure(tree), jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{got_s}\n{want_s}")
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape} {a.dtype} where the program "
                             f"has {b.shape} {b.dtype}")
    return tree


def serve_engine(cfg, params, t: dict, seed: int):
    return ServeEngine(cfg, params, max_len=t["max_len"], batch=t["batch"],
                       temperature=t["temperature"], seed=seed)


def train_state(cfg, params):
    """Parameters plus fresh AdamW moments; the parameters are donated."""
    return jax.jit(lambda p: init_train_state(cfg, p), donate_argnums=0)(params)


def train_step(cfg, t: dict):
    """The program's training step as its launcher jits it, state donated."""
    o = t["optimizer"]
    opt = AdamWConfig(lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
                      eps=o["eps"], weight_decay=o["weight_decay"],
                      grad_clip=o["grad_clip"], warmup_steps=o["warmup_steps"],
                      total_steps=o["total_steps"], min_lr_ratio=o["min_lr_ratio"])
    return jit_train_step(cfg, opt)


@contextlib.contextmanager
def constant_uniforms(value: float = 0.5):
    """A fault: every uniform the program draws reads ``value``."""
    real = kops.uniform

    def constant(seed, shape, *args, **kwargs):
        return jnp.full(shape, value, jnp.float32)

    kops.uniform = constant
    try:
        yield
    finally:
        kops.uniform = real

