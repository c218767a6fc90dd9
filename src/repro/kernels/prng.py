"""LCG and xoshiro128+ PRNGs as Pallas TPU kernels.

The paper's integer thread is PRN generation; here it runs on the VPU's
integer lanes.  Parallelization contract (identical in ``ref.py`` so the
kernels are bit-exact against the oracle):

* dense ``uniform``: counter-based — every element seeds its own stream from
  ``splitmix32(global_index + seed)`` and takes one generator step.  Blocks
  are independent, so the grid parallelizes perfectly (no sequential state
  crosses a block boundary — the COPIFT Step-4 tiling argument applied to
  PRNG reproducibility).
* Monte-Carlo kernels (montecarlo.py): lanes are sequential streams *within*
  a block (fori_loop), blocks re-seed by block index — the paper's
  sequential-PRNG structure inside each tile, tiles independent.

These kernels power the framework's data pipeline and dropout
(``repro.data``), so the Monte-Carlo machinery is the same code path that
feeds training.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import LCG_A, LCG_C

LANES = 1024
DEFAULT_BLOCK_ROWS = 64

_PHI = np.uint32(0x9e3779b9)


def _splitmix32(z):
    z = (z + _PHI).astype(jnp.uint32)
    z = (z ^ (z >> jnp.uint32(16))) * jnp.uint32(0x85ebca6b)
    z = (z ^ (z >> jnp.uint32(13))) * jnp.uint32(0xc2b2ae35)
    return z ^ (z >> jnp.uint32(16))


def _to_unit(bits):
    """uint32 → fp32 in [0, 1) from the top 24 bits.  Mosaic has no
    uint32 → float32 cast; the value is below 2**24, so going through
    int32 is exact."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _uniform_kernel(seed_ref, o_ref, *, kind: str, block_rows: int):
    # INT phase: global element counter → per-lane stream seed → one step.
    b = pl.program_id(0)
    base = (b * block_rows * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1))
    idx = base.astype(jnp.uint32) + seed_ref[0]
    if kind == "lcg":
        state = _splitmix32(idx)
        new = state * LCG_A + LCG_C
        bits = (new >> jnp.uint32(9)) ^ new
    else:  # xoshiro128+
        s0 = _splitmix32(idx)
        s1 = _splitmix32(idx + jnp.uint32(0x9e3779b9))
        s2 = _splitmix32(idx + jnp.uint32((2 * 0x9e3779b9) & 0xffffffff))
        s3 = _splitmix32(idx + jnp.uint32((3 * 0x9e3779b9) & 0xffffffff))
        bits = s0 + s3
    # FP phase: top-24-bit conversion to [0, 1).
    o_ref[...] = _to_unit(bits)


@functools.partial(jax.jit, static_argnames=("rows", "kind", "block_rows",
                                             "interpret", "shape"))
def uniform_2d(seed: jax.Array, rows: int | None = None, *, kind: str = "xoshiro128p",
               block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool = False,
               shape: tuple[int, int] | None = None) -> jax.Array:
    """Uniform [0,1) fp32 of shape (rows, LANES); ``seed`` uint32 scalar array."""
    if shape is None:
        shape = (rows, LANES)
    rows, lanes = shape
    assert lanes == LANES and rows % block_rows == 0
    kern = functools.partial(_uniform_kernel, kind=kind, block_rows=block_rows)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(jnp.asarray([seed], jnp.uint32).reshape(1))


def uniform_counter_ref(seed: int, shape: tuple[int, int],
                        kind: str = "xoshiro128p") -> jax.Array:
    """Oracle for uniform_2d (same counter-based construction, pure jnp)."""
    rows, lanes = shape
    idx = (jnp.arange(rows * lanes, dtype=jnp.uint32)
           + jnp.uint32(seed)).reshape(shape)
    if kind == "lcg":
        state = _splitmix32(idx)
        new = state * LCG_A + LCG_C
        bits = (new >> jnp.uint32(9)) ^ new
    else:
        s0 = _splitmix32(idx)
        s3 = _splitmix32(idx + jnp.uint32((3 * 0x9e3779b9) & 0xffffffff))
        bits = s0 + s3
    return _to_unit(bits)
