"""Seeded weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the program receives
them as inputs, and the reference makes the same ones again from the seed,
so the reference takes nothing that the program has made.  A weight is
``offset + scale * N(0, 1)`` in float32, drawn from a key folded from the
seed and the weight's place in the reference's ``layout``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def generate(layout: dict, k) -> dict:
    """The weights of ``layout`` (name -> (shape, scale, offset)) from key
    ``k``; traceable, so it can also run inside another jitted program."""
    out = {}
    for i, (name, (shape, scale, offset)) in enumerate(sorted(layout.items())):
        z = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
        out[name] = offset + scale * z
    return out


def make(layout: dict, seed: int) -> dict:
    """All weights of ``layout`` for ``seed``, on the default device."""
    return jax.jit(lambda k: generate(layout, k))(key(seed))
