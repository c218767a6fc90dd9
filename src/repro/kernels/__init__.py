"""Pallas TPU kernels for the paper's six evaluated computations + the
COPIFT softmax used by ``repro.models`` attention.

Layout (per kernel): ``<name>.py`` holds the ``pl.pallas_call`` + BlockSpec
tiling, ``ops.py`` the jit'd public wrappers with impl dispatch, ``ref.py``
the pure-jnp oracles.  Validation: ``tests/test_kernels.py`` (interpret-mode
execution on CPU; TPU is the compilation target).
"""

from repro.kernels import ops, ref
from repro.kernels.ops import (exp, log, mc_pi, mc_poly, overrides, softmax,
                               uniform)

__all__ = ["ops", "ref", "exp", "log", "mc_pi", "mc_poly", "overrides",
           "softmax", "uniform"]
