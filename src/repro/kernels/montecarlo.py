"""Monte-Carlo hit-and-miss integration as Pallas TPU kernels (pi / poly ×
lcg / xoshiro128+ — the paper's four MC kernels).

Structure inside one grid step (= one COPIFT block):

* INT phase (the paper's integer thread): ``iters`` sequential PRNG steps per
  lane on the VPU integer lanes — a true recurrence, kept lane-local.
* FP phase: uint32→fp32 conversion (the cft.fcvt analogue — lane-local
  ``astype``, no cross-domain round trip), scaling, evaluation (unit-circle
  test or polynomial), the ``flt.d`` comparison as a lane mask, accumulation
  into three rotating partial accumulators (the FP-latency-hiding trick the
  timing model also uses).

The two phases communicate through VREGs within the fori_loop — on Snitch
this traffic is the block buffer + SSR stream; on the VPU the crossing is
free, which is exactly the hardware-adaptation point of DESIGN.md §2.

Each grid step owns lanes seeded by (block, lane) via splitmix32, writes one
partial-sum row; the final reduction happens outside the kernel.  The same
blocked construction exists in ``ref.mc_blocked`` for bit-exact comparison.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.prng import _splitmix32, _to_unit
from repro.kernels.ref import LCG_A, LCG_C, MC_POLY_COEFFS

LANES = 1024


def _init_state(kind: str, block_id, seed, lane_iota):
    base = (lane_iota + block_id * jnp.uint32(LANES)) + seed
    if kind == "lcg":
        return (_splitmix32(base),)
    return tuple(_splitmix32(base + jnp.uint32((k * 0x9e3779b9) & 0xffffffff))
                 for k in range(4))


def _step(kind: str, state):
    if kind == "lcg":
        (s,) = state
        new = s * LCG_A + LCG_C
        out = (new >> jnp.uint32(9)) ^ new
        return (new,), out
    s0, s1, s2, s3 = state
    out = s0 + s3
    t = s1 << jnp.uint32(9)
    s2 = s2 ^ s0
    s3 = s3 ^ s1
    s1 = s1 ^ s2
    s0 = s0 ^ s3
    s2 = s2 ^ t
    s3 = (s3 << jnp.uint32(11)) | (s3 >> jnp.uint32(21))
    return (s0, s1, s2, s3), out


def _poly_eval(x):
    p = jnp.full_like(x, np.float32(MC_POLY_COEFFS[0]))
    for c in MC_POLY_COEFFS[1:]:
        p = p * x + np.float32(c)
    return p


def _mc_kernel(seed_ref, o_ref, *, kind: str, problem: str, iters: int):
    b = pl.program_id(0).astype(jnp.uint32)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, LANES), 1)[0]
    state = _init_state(kind, b, seed_ref[0], lane)
    accs = (jnp.zeros(LANES, jnp.float32),) * 3   # 3 rotating accumulators

    def body(i, carry):
        state, accs = carry
        # --- INT phase: two sequential draws (x, u) per sample.
        state, bx = _step(kind, state)
        state, bu = _step(kind, state)
        # --- FP phase: convert, scale, evaluate, compare, accumulate.
        x = _to_unit(bx)
        u = _to_unit(bu)
        if problem == "pi":
            hit = (x * x + u * u) < jnp.float32(1.0)
        else:
            hit = u < _poly_eval(x)
        k = i % 3
        accs = tuple(jnp.where(k == j, a + hit.astype(jnp.float32), a)
                     for j, a in enumerate(accs))
        return state, accs

    _, accs = jax.lax.fori_loop(0, iters, body, (state, accs))
    o_ref[...] = (accs[0] + accs[1] + accs[2]).reshape(1, 1, LANES)


@functools.partial(jax.jit,
                   static_argnames=("kind", "problem", "iters", "n_blocks",
                                    "interpret"))
def mc_partial_sums(seed: jax.Array, *, kind: str, problem: str, iters: int,
                    n_blocks: int, interpret: bool = False) -> jax.Array:
    """Per-block hit counts, shape (n_blocks, LANES).

    Each grid step writes a (1, 1, LANES) block of an (n_blocks, 1, LANES)
    array: a (1, LANES) block of an (n_blocks, LANES) array breaks the TPU
    rule that the second-to-last block dim divides by 8 or spans the
    array."""
    kern = functools.partial(_mc_kernel, kind=kind, problem=problem,
                             iters=iters)
    sums = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.float32),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(jnp.asarray([seed], jnp.uint32).reshape(1))
    return sums.reshape(n_blocks, LANES)


def mc_estimate(seed: int, *, kind: str, problem: str, n_samples: int,
                n_blocks: int = 8, interpret: bool = False) -> jax.Array:
    """π estimate (problem='pi') or ∫₀¹ f (problem='poly')."""
    iters = n_samples // (n_blocks * LANES)
    sums = mc_partial_sums(jnp.uint32(seed), kind=kind, problem=problem,
                           iters=iters, n_blocks=n_blocks, interpret=interpret)
    frac = jnp.sum(sums) / (iters * n_blocks * LANES)
    return 4.0 * frac if problem == "pi" else frac


def mc_blocked_ref(seed: int, *, kind: str, problem: str, iters: int,
                   n_blocks: int) -> jax.Array:
    """Pure-jnp oracle with the kernel's exact blocked construction."""
    lane = jnp.arange(LANES, dtype=jnp.uint32)
    rows = []
    for b in range(n_blocks):
        state = _init_state(kind, jnp.uint32(b), jnp.uint32(seed), lane)
        acc = jnp.zeros(LANES, jnp.float32)
        for i in range(iters):
            state, bx = _step(kind, state)
            state, bu = _step(kind, state)
            x, u = _to_unit(bx), _to_unit(bu)
            hit = (x * x + u * u) < 1.0 if problem == "pi" else u < _poly_eval(x)
            acc = acc + hit.astype(jnp.float32)
        rows.append(acc)
    return jnp.stack(rows)
