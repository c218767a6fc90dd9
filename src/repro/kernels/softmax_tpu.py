"""COPIFT softmax as a Pallas TPU kernel — the paper's LLM bridge.

Paper §III-A: vectorized expf "is the main component of softmax operations,
which consume a considerable fraction of cycles in modern LLMs."  This
kernel embeds the COPIFT exp construction (FP scale/round → INT exponent
assembly → FP polynomial) inside a numerically-stable row softmax, and is
what ``repro.models`` attention uses when ``use_copift_softmax`` is set.

Tiling: grid over row blocks; each grid step holds (block_rows, cols) in
VMEM.  Row-internal reductions (max/sum) run on the VPU; the three COPIFT
phases of the exp are as in ``expf.py``.  A block may hold at most
``MAX_BLOCK_ELEMS`` elements: on a TPU v5e, 8 rows × 65 536 fp32 columns
compile and 8 × 131 072 exceed the scoped VMEM.  Longer rows raise
``ValueError``; there is no fallback path.

The backward pass is ``y * (g - Σ g·y)`` in plain jnp (``jax.custom_vjp``),
so ``jax.grad`` goes through the kernel's forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import _EXP2_POLY, _LN2_HI, _LN2_LO, _LOG2E


def _exp_phases(r_in):
    """The COPIFT exp construction on an arbitrary-shape fp32 array."""
    z = r_in * _LOG2E
    kd = jnp.round(z)
    r = (r_in - kd * _LN2_HI) - kd * _LN2_LO
    ki = jnp.clip(kd.astype(jnp.int32), -126, 127)
    s = jax.lax.bitcast_convert_type(
        jnp.left_shift(ki + jnp.int32(127), 23), jnp.float32)
    p = jnp.full_like(r, _EXP2_POLY[0])
    for c in _EXP2_POLY[1:]:
        p = p * r + c
    y = (p * r + jnp.float32(1.0)) * s
    return jnp.where(r_in < -87.0, 0.0, y)


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = _exp_phases(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


#: Elements one grid step may hold (8 rows × 64 k fp32 columns).
MAX_BLOCK_ELEMS = 8 * 65536


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _softmax_rows(x, block_rows, interpret):
    rows, cols = x.shape
    return pl.pallas_call(
        _softmax_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        interpret=interpret,
    )(x)


def _softmax_rows_fwd(x, block_rows, interpret):
    y = _softmax_rows(x, block_rows, interpret)
    return y, y


def _softmax_rows_bwd(block_rows, interpret, y, g):
    yf, gf = y.astype(jnp.float32), g.astype(jnp.float32)
    dx = yf * (gf - jnp.sum(gf * yf, axis=-1, keepdims=True))
    return (dx.astype(y.dtype),)


_softmax_rows.defvjp(_softmax_rows_fwd, _softmax_rows_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def softmax_2d(x: jax.Array, block_rows: int = 8,
               interpret: bool = False) -> jax.Array:
    """Row softmax over (rows, cols); rows % block_rows == 0."""
    rows, cols = x.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} is not a multiple of "
                         f"block_rows={block_rows}")
    if block_rows * cols > MAX_BLOCK_ELEMS:
        raise ValueError(
            f"a ({block_rows}, {cols}) softmax block exceeds the kernel's "
            f"VMEM limit of {MAX_BLOCK_ELEMS} elements (rows of at most "
            f"{MAX_BLOCK_ELEMS // 8} columns at 8 rows per block)")
    return _softmax_rows(x, block_rows, interpret)
