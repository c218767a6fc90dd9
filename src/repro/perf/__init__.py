"""``repro.perf`` — the timing-engine performance layer.

The reproduction's evaluation pipeline is itself a workload: the tune cost
oracle, ``api.evaluate``, ``api.sweep`` and the serving simulator all
bottom out in the pure-Python discrete-event simulator in
``core.timing``, and the paper's Table-I exploration (and the Late
Breaking Results follow-up) hinge on pricing large schedule spaces.  This
package makes that pipeline fast *without changing a single cycle*:

* :mod:`repro.perf.memo` — the content-addressed simulation memo that
  ``core.timing`` consults (``STREAM_MEMO`` / ``TIMING_MEMO``), with the
  process-wide on/off switch (``$REPRO_TIMING_MEMO``,
  :func:`set_enabled`, :func:`memo_disabled`) and :func:`stats`.
* :func:`evaluate_batch` — the batched cost oracle
  (``repro.tune.cost.evaluate_batch``): many candidates priced in one
  pass, grouped by shared sub-simulations, the cluster math composed with
  numpy over the candidate axis.
* :func:`sweep` — the batched target evaluator
  (``repro.api.sweep``): many :class:`~repro.api.Target`\\ s priced in one
  vectorized pass over shared per-kernel timings.

The batch entry points live with their subsystems (``tune`` / ``api``)
and are re-exported here lazily, so importing ``repro.perf`` from
``core.timing`` never creates an import cycle.

Parity is the contract: every memoized / batched path returns bit-for-bit
the numbers the cold scalar path returns (pinned by
``tests/test_perf.py`` and the hypothesis property tests in
``tests/test_timing_energy.py``).
"""

from repro.perf.memo import (STREAM_MEMO, TIMING_MEMO, SimMemo, clear_all,
                             enabled, memo_disabled, set_enabled, stats)

__all__ = [
    "STREAM_MEMO", "TIMING_MEMO", "SimMemo",
    "enabled", "set_enabled", "memo_disabled", "clear_all", "stats",
    "evaluate_batch", "sweep",
]

_LAZY = {
    "evaluate_batch": ("repro.tune.cost", "evaluate_batch"),
    "sweep": ("repro.api.evaluate", "sweep"),
}


def __getattr__(name: str):
    """Lazy re-exports of the subsystem-hosted batch entry points."""
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}") from None
    import importlib
    return getattr(importlib.import_module(mod_name), attr)
