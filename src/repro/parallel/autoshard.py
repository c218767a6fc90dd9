"""Activation sharding constraints.

XLA's sharding propagation reliably shards parameters (they arrive with
NamedShardings) but can drop the batch axis on large intermediates inside
scans (layer stack, chunked attention, chunked CE).  This module provides a
trace-time context carrying the mesh's logical axes; model code calls
``hidden()``/``scores()``/``logits()`` to pin the batch (or sequence, in
SP mode) dimension wherever a big tensor is born.  Without an active
context every call is a no-op — single-device tests never see a mesh.
"""

from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass

import jax
from jax.sharding import PartitionSpec as P

_TLS = threading.local()


@dataclass(frozen=True)
class ActivationSharding:
    dp: tuple[str, ...]            # data-parallel axes for the batch dim
    tp: str | None = "model"       # tensor-parallel axis
    seq_sharded: bool = False      # SP: shard T instead of B (long_500k)
    mesh: object = None

    def axis_size(self, name: str) -> int:
        return self.mesh.shape.get(name, 1) if self.mesh is not None else 1


def current() -> ActivationSharding | None:
    return getattr(_TLS, "ctx", None)


@contextmanager
def activation_sharding(mesh, dp=("data",), tp="model", seq_sharded=False):
    prev = current()
    _TLS.ctx = ActivationSharding(dp=tuple(dp), tp=tp,
                                  seq_sharded=seq_sharded, mesh=mesh)
    try:
        yield
    finally:
        _TLS.ctx = prev


def _constrain(x, spec: P):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError):
        return x


def _dp_size(ctx) -> int:
    n = 1
    for a in ctx.dp:
        n *= ctx.axis_size(a)
    return n


def hidden(x):
    """(B, T, D) residual stream."""
    ctx = current()
    if ctx is None or x.ndim != 3:
        return x
    if ctx.seq_sharded and x.shape[1] % _dp_size(ctx) == 0:
        return _constrain(x, P(None, ctx.dp, None))
    if x.shape[0] % _dp_size(ctx) == 0:
        return _constrain(x, P(ctx.dp, None, None))
    return x


def scores(s):
    """(B, Hkv, g, T, C) attention scores/probs inside chunked attention."""
    ctx = current()
    if ctx is None or s.ndim != 5:
        return s
    if s.shape[0] % _dp_size(ctx) != 0:
        return s
    m = ctx.tp if ctx.tp and ctx.tp not in ctx.dp and ctx.axis_size(ctx.tp) \
        else None
    for dim in (1, 2):
        if m and s.shape[dim] % ctx.axis_size(m) == 0:
            spec = [ctx.dp, None, None, None, None]
            spec[dim] = m
            return _constrain(s, P(*spec))
    return _constrain(s, P(ctx.dp, None, None, None, None))


def logits(x):
    """(B, T, V) (or (B, chunk, V)) readout."""
    ctx = current()
    if ctx is None or x.ndim != 3:
        return x
    m = ctx.tp if (ctx.tp and ctx.tp not in ctx.dp
                   and ctx.axis_size(ctx.tp)
                   and x.shape[-1] % ctx.axis_size(ctx.tp) == 0) else None
    if ctx.seq_sharded and x.shape[1] % _dp_size(ctx) == 0:
        return _constrain(x, P(None, ctx.dp, m))
    if x.shape[0] % _dp_size(ctx) == 0:
        return _constrain(x, P(ctx.dp, None, m))
    return x


def barrier(x):
    """Optimization barrier under an active mesh context: pins the bf16
    downcast on the producer side of SPMD-inserted collectives (XLA's CPU
    cost model otherwise commutes converts across all-reduce, turning the
    TP partial-sum reduction into fp32 — 2× the ICI traffic).  §Perf it.2."""
    if current() is None:
        return x
    return jax.lax.optimization_barrier(x)


def tokens_nd(x):
    """(B, T) / (B, T, D) data inputs."""
    ctx = current()
    if ctx is None:
        return x
    if ctx.seq_sharded and x.ndim >= 2 and x.shape[1] % _dp_size(ctx) == 0:
        return _constrain(x, P(None, ctx.dp, *([None] * (x.ndim - 2))))
    if x.shape[0] % _dp_size(ctx) == 0:
        return _constrain(x, P(ctx.dp, *([None] * (x.ndim - 1))))
    return x


def rowwise(fn, x):
    """``fn(x)`` for a kernel that treats each index of x's leading dim on
    its own.  Mosaic kernels cannot be partitioned automatically, so under
    a multi-device mesh (``jax.set_mesh``) ``fn`` runs in a ``shard_map``
    that splits the leading dim over the largest prefix of the
    data-parallel axes dividing it (replicated over the mesh if none
    does)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn(x)
    ctx = current()
    lead = ()
    for a in ctx.dp if ctx is not None else mesh.axis_names:
        if x.shape[0] % math.prod(mesh.shape[b] for b in lead + (a,)):
            break
        lead += (a,)
    spec = P(lead or None, *([None] * (x.ndim - 1)))
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)
