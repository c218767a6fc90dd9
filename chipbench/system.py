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
import importlib
import sys
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


def adapter(c: dict):
    return importlib.import_module(f"chipbench.adapters.{c['reference']}")


def program_config(c: dict):
    """The registry's architecture ``c['arch']`` at the sizes and
    precisions the file states.  Raises where the architecture's fixed
    features (norm, tied head, activation, rotary base) differ from the
    file."""
    base = load_config(c["arch"])
    cfg = base.replace(
        dtype=c["compute_dtype"], param_dtype=c["param_dtype"],
        n_layers=c["num_hidden_layers"], layer_types="a" * c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"])
    want = {"norm": _NORMS.get(c["norm"]), "tie_embeddings": c["tie_word_embeddings"],
            "act": c["hidden_act"], "rope": "rope", "rope_theta": c["rope_theta"],
            "qk_norm": False, "sliding_window": 0, "moe": None, "ssm": None}
    wrong = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
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

