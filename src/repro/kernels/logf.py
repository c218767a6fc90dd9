"""COPIFT log as a Pallas TPU kernel — the ISSR (indirect stream) kernel.

logf's distinguishing feature in the paper (Table I, ‡): its Type-1
dependencies — table gathers at integer-computed indices — map to **ISSRs**.
The TPU analogue is an in-kernel lookup in a table held in scalar memory:
the 16-entry invc/logc tables ride in as whole-array SMEM operands (one
copy, reused every block) and the integer phase's index vector drives a
16-way select, one ``where`` per table entry.  Mosaic lowers no 1-D
vector gather, and the select costs 16 VPU ops per vreg.

Phase structure: INT₀ (bit manipulation: re-bias, window index, exponent
extraction, mantissa masking) → [ISSR gather] → FP₁ (r = z·invc − 1,
degree-4 log1p polynomial, + logc + k·ln2) — exactly the paper's logf
partition (Fig. 1 analogue; our Table-I transcription has the same shape).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (LOGF_INVC, LOGF_LOGC, _LN2, _LOG1P_POLY,
                               _LOGF_OFF, _LOGF_TABLE_BITS)

LANES = 1024
DEFAULT_BLOCK_ROWS = 64


def _log_kernel(x_ref, invc_ref, logc_ref, o_ref):
    x = x_ref[...]
    # --- INT phase 0: bit manipulation (glibc logf).
    ix = jax.lax.bitcast_convert_type(x, jnp.int32)
    tmp = ix - _LOGF_OFF
    i = jnp.right_shift(tmp, 23 - _LOGF_TABLE_BITS) & jnp.int32(
        (1 << _LOGF_TABLE_BITS) - 1)
    k = jnp.right_shift(tmp, 23)
    iz = ix - (tmp & jnp.int32(np.int32(np.uint32(0xff800000))))
    z = jax.lax.bitcast_convert_type(iz, jnp.float32)
    # --- ISSR: indirect streams invc[i], logc[i] driven by the index vector.
    invc = jnp.zeros_like(z)
    logc = jnp.zeros_like(z)
    for j in range(1 << _LOGF_TABLE_BITS):
        hit = i == j
        invc = jnp.where(hit, invc_ref[j], invc)
        logc = jnp.where(hit, logc_ref[j], logc)
    # --- FP phase 1.
    r = z * invc - jnp.float32(1.0)
    p = jnp.full_like(r, _LOG1P_POLY[0])
    for c in _LOG1P_POLY[1:]:
        p = p * r + c
    y = (p * r + jnp.float32(1.0)) * r
    o_ref[...] = y + logc + k.astype(jnp.float32) * _LN2


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def log_2d(x: jax.Array, block_rows: int = DEFAULT_BLOCK_ROWS,
           interpret: bool = False) -> jax.Array:
    """ln over a (rows, LANES) fp32 array of positive normals."""
    rows, lanes = x.shape
    assert lanes == LANES and rows % block_rows == 0, (x.shape, block_rows)
    table = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _log_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                  table, table],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(x.astype(jnp.float32), LOGF_INVC, LOGF_LOGC)
