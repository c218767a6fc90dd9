"""``repro.obs`` — issue-slot tracing, stall-breakdown metrics, and
profiling spans over the whole reproduction stack.

Three opt-in layers behind one front door (:func:`session`):

* **event tracing** (``obs.record``) — per-instruction issue events on
  named lanes (int core / FPSS / rv32g baseline) with stall classes (RAW,
  write-port, TCDM contention, FREP launch), recorded by the discrete-event
  simulator in ``core.timing``;
* **metrics** (``obs.metrics``) — a process-wide counter/gauge/histogram
  registry fed by ``core.timing`` (stall split), ``cluster.contention`` /
  ``cluster.dma``, ``tune.cost`` / ``tune.search`` (oracle throughput,
  rung progress), ``perf.memo`` (warmth) and ``serve.engine`` (cache
  donation, weights cast at build);
* **spans** (``obs.spans``) — nested wall-time scopes with per-span memo
  provenance, wrapping ``api.evaluate``/``api.sweep``, tuner searches and
  the serve engine's host steps.

Everything is zero-cost-by-default: disabled, the hooks reduce to a couple
of ContextVar reads per *call* (never per instruction), gated < 5 % by
``benchmarks/obs_bench.py``.  Traced runs never bypass or poison the
``repro.perf`` memo — they re-simulate (bit-identical by construction) and
record hit/cold provenance, with parity pinned in ``tests/test_obs.py``.

Exports go to Perfetto/Chrome-trace JSON (:meth:`Session.save`) or a
terminal timeline; ``python -m repro.obs.trace <kernel>`` does both from
the command line.

On top of the single-run layers sit the *differential* ones:

* **attribution** (``obs.attrib``) — exact stall-category waterfalls
  between two traced runs (plan A vs plan B, Target A vs B), step deltas
  summing bit-for-bit to the ``Report`` cycle delta;
* **history** (``obs.history``) — an append-only JSONL metric store with
  rolling-baseline regression detection (the CI gate);
* **report** (``obs.report``) — a self-contained HTML report (timeline,
  stall bars, waterfall, trend sparklines) plus a terminal summary.
"""

from repro.obs import record as record              # noqa: F401
from repro.obs import metrics as metrics            # noqa: F401
from repro.obs import spans as spans                # noqa: F401
from repro.obs import export as export              # noqa: F401
from repro.obs import attrib as attrib              # noqa: F401
from repro.obs import history as history            # noqa: F401
from repro.obs.record import (TraceRecorder, active_recorder,  # noqa: F401
                              hooks_bypassed, recording)
from repro.obs.metrics import REGISTRY              # noqa: F401
from repro.obs.spans import span                    # noqa: F401
from repro.obs.export import (chrome_trace, reconcile,  # noqa: F401
                              render_timeline, save_chrome_trace)
from repro.obs.attrib import (Attribution, attribute,  # noqa: F401
                              attribute_evaluate, attribute_plans)
from repro.obs.history import (append_snapshot, detect_regressions,  # noqa: F401,E501
                               read_history)
from repro.obs.session import Session, session      # noqa: F401

__all__ = [
    "session", "Session", "span",
    "TraceRecorder", "active_recorder", "recording", "hooks_bypassed",
    "REGISTRY", "chrome_trace", "save_chrome_trace", "render_timeline",
    "reconcile", "record", "metrics", "spans", "export",
    "Attribution", "attribute", "attribute_evaluate", "attribute_plans",
    "attrib", "history", "append_snapshot", "detect_regressions",
    "read_history",
]
