"""Public jit'd wrappers for the COPIFT kernels.

Implementation selection (``impl=``):

* ``"pallas"``     — the Pallas TPU kernels; on a CPU backend they execute in
  ``interpret=True`` mode (the kernel body runs as traced jnp — correctness
  path for this container; TPU is the performance target).
* ``"reference"``  — the pure-jnp oracles from ``ref.py``.  Used by the
  512-device dry-run lowers (keeps the HLO free of interpreter while-loops)
  and as the allclose baseline in tests.
* ``"auto"``       — pallas on TPU, reference elsewhere (the default for the
  model stack; the kernels' correctness is proven separately in
  tests/test_kernels.py which forces interpret mode).

Shapes: the public entry points accept arbitrary shapes; internally arrays
are flattened and padded to the (rows, 1024) vreg-tiled layout the kernels
use, then unpadded.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import expf as _exp
from repro.kernels import logf as _log
from repro.kernels import montecarlo as _mc
from repro.kernels import prng as _prng
from repro.kernels import ref as _ref
from repro.kernels import softmax_tpu as _softmax
from repro.parallel import autoshard

LANES = _exp.LANES

_IMPLS = ("auto", "pallas", "reference")

#: Two layers of configuration.  Scoped overrides (``overrides`` /
#: ``repro.api.config``) live in ContextVars: a ``with`` block in one
#: thread or asyncio task cannot race a concurrent benchmark reading the
#: default in another — the failure mode the old mutable globals invited.
#: The *process-wide defaults* underneath (``set_impl`` /
#: ``set_tuned_defaults``) stay plain module globals, visible from every
#: thread: ``ServeEngine(autotune=True)`` sets them in ``__init__`` and
#: the lazily-resolved jit traces must still see them when ``generate()``
#: runs on a request thread (new threads start with empty contexts, so a
#: ContextVar default would silently vanish there).
_IMPL_DEFAULT = "auto"
_TUNED_DEFAULT = False
_IMPL_VAR: contextvars.ContextVar[str | None] = \
    contextvars.ContextVar("repro_kernels_impl", default=None)
_TUNED_VAR: contextvars.ContextVar[bool | None] = \
    contextvars.ContextVar("repro_kernels_tuned_defaults", default=None)


def current_impl() -> str:
    """The impl default in effect: the innermost scoped override, else the
    process-wide default."""
    v = _IMPL_VAR.get()
    return _IMPL_DEFAULT if v is None else v


def tuned_defaults_enabled() -> bool:
    v = _TUNED_VAR.get()
    return _TUNED_DEFAULT if v is None else v


def set_impl(impl: str) -> None:
    """Set the process-wide impl default ('auto' | 'pallas' |
    'reference'), visible from every thread.  Prefer the scoped
    ``repro.api.config(impl=...)`` where a ``with`` block suffices."""
    global _IMPL_DEFAULT
    if impl not in _IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {_IMPLS}")
    _IMPL_DEFAULT = impl


def set_tuned_defaults(enable: bool = True) -> bool:
    """Let the autotuner (``repro.tune``) pick the kernels' default block
    tiling — the process-wide default, visible from every thread.  Entry
    points called without an explicit ``block_rows`` then scale the module
    default by the tuned block's share of the Table-I cap (the analytic
    model's block choice transferred onto the Pallas grid); tuned results
    come from the persistent tune cache, so the first call per kernel
    searches and the rest are free.  Prefer the scoped
    ``repro.api.config(...)`` unless the enablement must outlive a
    ``with`` block (e.g. ``ServeEngine`` setup, whose jit traces resolve
    tilings lazily at first generate, possibly on another thread).

    Returns the *previous* process-wide default, so callers that must use
    the persistent setter can still restore the state they found
    (``ServeEngine.close()`` does exactly this)."""
    global _TUNED_DEFAULT
    prev = _TUNED_DEFAULT
    _TUNED_DEFAULT = bool(enable)
    _tuned_block_rows.cache_clear()
    return prev


@contextlib.contextmanager
def overrides(impl: str | None = None, tuned_defaults: bool | None = None):
    """Scoped kernel-config override — the engine behind
    ``repro.api.config``.  ``None`` leaves a setting untouched; values are
    restored (and the tuned-tiling memo dropped) on exit, even on error."""
    tokens = []
    if impl is not None:
        if impl not in _IMPLS:
            raise ValueError(f"unknown impl {impl!r}; expected one of "
                             f"{_IMPLS}")
        tokens.append((_IMPL_VAR, _IMPL_VAR.set(impl)))
    if tuned_defaults is not None:
        tokens.append((_TUNED_VAR, _TUNED_VAR.set(bool(tuned_defaults))))
        _tuned_block_rows.cache_clear()
    try:
        yield
    finally:
        for var, token in reversed(tokens):
            var.reset(token)
        if tuned_defaults is not None:
            _tuned_block_rows.cache_clear()


@functools.lru_cache(maxsize=None)
def _tuned_block_rows(kernel: str, default_rows: int) -> int:
    # The facade's default tuner: one shared cache + cost oracle across
    # ops/copift/engine consumers (repro.api.default_tuner).
    from repro.api import default_tuner
    tuner = default_tuner()
    w = tuner._workload(kernel)
    res = tuner.block(w)          # only the block transfers to the tiling
    return max(1, round(default_rows * res.best.block / w.max_block))


def _resolve_rows(kernel: str, explicit: int | None, default_rows: int) -> int:
    if explicit is not None:
        return explicit
    if tuned_defaults_enabled():
        try:
            return _tuned_block_rows(kernel, default_rows)
        except (ImportError, KeyError):
            pass
    return default_rows


def _resolve(impl: str | None) -> str:
    impl = impl or current_impl()
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return impl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile_1d(x: jax.Array, block_rows: int):
    """Flatten + pad to (rows, LANES) with rows % block_rows == 0."""
    n = x.size
    tile = block_rows * LANES
    padded = -(-n // tile) * tile
    flat = jnp.pad(x.reshape(-1), (0, padded - n))
    return flat.reshape(-1, LANES), n


def _untile(y: jax.Array, n: int, shape, dtype):
    return y.reshape(-1)[:n].reshape(shape).astype(dtype)


def exp(x: jax.Array, impl: str | None = None,
        block_rows: int | None = None) -> jax.Array:
    """COPIFT exp (glibc-expf-style), elementwise, any shape."""
    if _resolve(impl) == "reference":
        return _ref.exp_ref(x).astype(x.dtype)
    block_rows = _resolve_rows("expf", block_rows, _exp.DEFAULT_BLOCK_ROWS)
    tiled, n = _tile_1d(x, block_rows)
    y = _exp.exp_2d(tiled, block_rows=block_rows, interpret=_interpret())
    return _untile(y, n, x.shape, x.dtype)


def log(x: jax.Array, impl: str | None = None,
        block_rows: int | None = None) -> jax.Array:
    """COPIFT log (glibc-logf-style, ISSR table gather), positive normals."""
    if _resolve(impl) == "reference":
        return _ref.log_ref(x).astype(x.dtype)
    block_rows = _resolve_rows("logf", block_rows, _log.DEFAULT_BLOCK_ROWS)
    tiled, n = _tile_1d(x, block_rows)
    tiled = jnp.where(tiled <= 0, 1.0, tiled)   # padding lanes → ln(1)=0
    y = _log.log_2d(tiled, block_rows=block_rows, interpret=_interpret())
    return _untile(y, n, x.shape, x.dtype)


def softmax(x: jax.Array, axis: int = -1, impl: str | None = None,
            block_rows: int | None = None) -> jax.Array:
    """COPIFT softmax.  Pallas path: 2-D row-tiled kernel over the last
    axis (another axis is moved last and back), rows zero-padded to a
    multiple of the (8-aligned) block.  Rows longer than the kernel's VMEM
    limit raise ``ValueError``."""
    if _resolve(impl) == "reference":
        return _ref.softmax_ref(x, axis=axis)
    if axis not in (-1, x.ndim - 1):
        y = softmax(jnp.moveaxis(x, axis, -1), -1, impl, block_rows)
        return jnp.moveaxis(y, -1, axis)
    cols = x.shape[-1]
    # TPU blocks span a multiple of 8 rows; shrink toward 8 for long rows.
    br = _resolve_rows("softmax", block_rows, 8)
    br = max(8, min(br, _softmax.MAX_BLOCK_ELEMS // cols) // 8 * 8)
    interpret = _interpret()

    def rows_softmax(xs):
        x2 = xs.reshape(-1, cols)
        rows = x2.shape[0]
        x2 = jnp.pad(x2, ((0, -rows % br), (0, 0)))
        y = _softmax.softmax_2d(x2, block_rows=br, interpret=interpret)
        return y[:rows].reshape(xs.shape)

    lead = x if x.ndim >= 2 else x.reshape(1, cols)   # rows on a leading dim
    return autoshard.rowwise(rows_softmax, lead).reshape(x.shape)


def uniform(seed: int | jax.Array, shape: tuple[int, ...],
            kind: str = "xoshiro128p", impl: str | None = None,
            block_rows: int | None = None) -> jax.Array:
    """Deterministic counter-based uniforms in [0, 1) (paper's PRNGs)."""
    n = int(np.prod(shape))
    if _resolve(impl) == "reference":
        rows = -(-n // LANES)
        u = _prng.uniform_counter_ref(int(seed) if not hasattr(seed, "dtype")
                                      else seed, (rows, LANES), kind=kind)
        return u.reshape(-1)[:n].reshape(shape)
    block_rows = _resolve_rows("prng", block_rows, _prng.DEFAULT_BLOCK_ROWS)
    tile = block_rows * LANES
    rows = (-(-n // tile)) * block_rows
    u = _prng.uniform_2d(jnp.uint32(seed), kind=kind, block_rows=block_rows,
                         interpret=_interpret(), shape=(rows, LANES))
    return u.reshape(-1)[:n].reshape(shape)


def mc_pi(seed: int, n_samples: int, kind: str = "xoshiro128p",
          n_blocks: int = 8, impl: str | None = None) -> jax.Array:
    """π via hit-and-miss MC (paper §III-A)."""
    if _resolve(impl) == "reference":
        iters = n_samples // (n_blocks * LANES)
        sums = _mc.mc_blocked_ref(seed, kind=kind, problem="pi", iters=iters,
                                  n_blocks=n_blocks)
        return 4.0 * jnp.sum(sums) / (iters * n_blocks * LANES)
    return _mc.mc_estimate(seed, kind=kind, problem="pi",
                           n_samples=n_samples, n_blocks=n_blocks,
                           interpret=_interpret())


def mc_poly(seed: int, n_samples: int, kind: str = "xoshiro128p",
            n_blocks: int = 8, impl: str | None = None) -> jax.Array:
    """∫₀¹ f for the Table-I polynomial via hit-and-miss MC."""
    if _resolve(impl) == "reference":
        iters = n_samples // (n_blocks * LANES)
        sums = _mc.mc_blocked_ref(seed, kind=kind, problem="poly", iters=iters,
                                  n_blocks=n_blocks)
        return jnp.sum(sums) / (iters * n_blocks * LANES)
    return _mc.mc_estimate(seed, kind=kind, problem="poly",
                           n_samples=n_samples, n_blocks=n_blocks,
                           interpret=_interpret())
