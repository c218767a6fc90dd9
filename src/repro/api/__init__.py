"""``repro.api`` — the single public front door.

The paper's pipeline — COPIFT plan → dual-issue timing → cluster/DVFS
evaluation → autotuning → serving — used to be reachable only through
parallel subsystem entry points (per-layer evaluate functions, three tuner
front doors, string-keyed kernels, ad-hoc engine kwargs).  This package is
the composable surface over all of it, built from three objects:

* :class:`KernelSpec` — *what* runs: one registry object per kernel
  binding its ISA schedule, tunable workload, jit'd entry point and
  reference oracle (``kernel("softmax")``; ``register_kernel`` for user
  kernels).
* :class:`Target`     — *where* it runs: cluster shape x DVFS point(s) x
  scheduling strategy x power cap.  Heterogeneous DVFS islands are the
  general case; a homogeneous cluster is a 1-island target and a single
  PE the 1-core cluster, exactly as Snitch treats a lone core.  One level
  up, ``Target.system(...)`` attaches a :class:`SystemConfig` — N
  clusters behind an interconnect + shared HBM (``repro.system``) — and
  the lone cluster is *its* 1-cluster degenerate case.
* :class:`Report`     — *what happened*: the one result dataclass
  :func:`evaluate` returns, with every derived metric defined once.

Plus the verbs: :func:`evaluate` (the one cluster-evaluation code path),
:func:`sweep` (many targets in one batched pass — same numbers, shared
timings), :class:`Tuner` (plan/block/operating-point/cluster-count searches
sharing one cache and one batched cost oracle), and :func:`config`
(scoped kernel-runtime overrides).  The pre-facade shims were removed
after PR 8 — README's migration table maps the historical names onto
these entry points.  The memo/batch tier underneath all of it is
``repro.perf`` (disable with ``REPRO_TIMING_MEMO=0``).
"""

from repro.api.evaluate import compare_strategies, evaluate, headline, sweep
from repro.api.registry import (KernelSpec, kernel, kernels,
                                register_kernel, specs)
from repro.api.report import Report, ReportMetrics
from repro.api.runtime import config
from repro.api.target import Target
from repro.api.tuner import Tuner

# Re-exported building blocks: the static cluster/system vocabulary a
# Target is built from, so facade consumers don't need to reach into
# repro.cluster / repro.system.
from repro.cluster.topology import (NOMINAL_POINT, OPERATING_POINTS,
                                    SNITCH_CLUSTER, ClusterConfig, DvfsIsland,
                                    OperatingPoint, parse_islands)
from repro.resilience.faults import (AllCoresDeadError, FaultState,
                                     FaultTrace, make_faults)
from repro.system.topology import SystemConfig, parse_system

_DEFAULT_TUNER: "Tuner | None" = None


def default_tuner() -> Tuner:
    """The shared process-wide :class:`Tuner` (default target, persistent
    cache) — what ``copift.make_plan(tune=True)`` consults, so every
    consumer hits one cache and one cost oracle."""
    global _DEFAULT_TUNER
    if _DEFAULT_TUNER is None:
        _DEFAULT_TUNER = Tuner()
    return _DEFAULT_TUNER


__all__ = [
    "KernelSpec", "kernel", "kernels", "register_kernel", "specs",
    "Target", "Report", "ReportMetrics",
    "evaluate", "sweep", "compare_strategies", "headline",
    "Tuner", "default_tuner", "config",
    "NOMINAL_POINT", "OPERATING_POINTS", "SNITCH_CLUSTER", "ClusterConfig",
    "DvfsIsland", "OperatingPoint", "parse_islands",
    "SystemConfig", "parse_system",
    "FaultTrace", "FaultState", "make_faults", "AllCoresDeadError",
]
