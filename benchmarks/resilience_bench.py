"""Benchmark: serving failover under deterministic fault injection.

One calibrated chaos scenario — a steady Poisson load on an 8-core
cluster, three fail-stop core deaths mid-trace — served two ways:

* **naive**    — the plain static policy, no retry: a killed batch's
  requests are lost outright and every loss is an SLO violation.
* **failover** — the same static plan wrapped in
  ``FailoverPolicy(headroom_slots=1)`` with a bounded
  retry/timeout/backoff ``RetryPolicy``: killed requests re-enqueue,
  partitions remap onto the survivors at the next control epoch, and the
  pre-bought headroom absorbs the lost capacity.

The acceptance inequality this benchmark exists to witness (and which
``main`` gates with exit 1): **failover completes >= the naive policy's
completed fraction with strictly fewer ``slo_violations``** on the
calibrated fault trace (validated across seeds 3/11/42/123), the
failover run replays bit-for-bit (determinism), and the *no-fault* serve
run — the PR 8 ``serve_bench`` scenario with an empty ``FaultTrace`` —
reproduces the fault-free percentile table bit-for-bit (the empty trace
must be the identity on the serving loop, not merely close).

CLI:
    PYTHONPATH=src python benchmarks/resilience_bench.py            # full
    PYTHONPATH=src python benchmarks/resilience_bench.py --smoke    # CI
    PYTHONPATH=src python benchmarks/resilience_bench.py --json -
"""

from __future__ import annotations

import argparse
import json
import sys

#: The calibrated chaos scenario.  Rate 1500 rps on 4x2-core slots at
#: 1 GHz (capacity ~2700 rps) keeps slots busy without saturating; the
#: two deaths at t=60 land inside in-flight batches and the third at
#: t=120 forces a second remap.  SLO 25 ms leaves retried requests room
#: to complete in-budget, so every naive loss is a violation failover
#: avoids.  Validated across seeds 3/11/42/123.
TRACE_SPEC = "poisson:rate=1500,kernel=softmax,elems=65536"
TRACE_SEED = 11
DURATION_MS = 200.0
SMOKE_DURATION_MS = 200.0   # one scenario; smoke == full minus reruns
FAULT_SPEC = "corefail@60:c0.0,corefail@60:c0.1,corefail@120:c0.2"
SLO_P99_MS = 25.0
EPOCH_MS = 10.0
QUEUE_CAP = 256
HEADROOM_SLOTS = 1
RETRY = dict(max_attempts=3, timeout_ms=25.0, backoff=2.0,
             base_delay_ms=0.5)

_LAST_DOC: dict | None = None


def _row(rep) -> dict:
    return dict(
        policy=rep.policy,
        requests=rep.n_requests,
        completed=rep.n_completed,
        completed_frac=rep.completed_frac,
        dropped=rep.n_dropped,
        lost=rep.n_lost,
        retried=rep.n_retried,
        batches_killed=rep.n_failed,
        failovers=rep.failovers,
        p50_ms=rep.latency_ms["p50"],
        p99_ms=rep.latency_ms["p99"],
        max_ms=rep.max_latency_ms,
        energy_uj=rep.energy_uj,
        slo_violations=rep.slo_violations,
        slo_met=rep.slo_met)


def _nofault_reproduction(pricer) -> dict:
    """The PR 8 pin: the serve_bench static-policy scenario priced with
    ``faults=`` an *empty* trace must reproduce the fault-free percentile
    table (and the full latency series) bit-for-bit."""
    try:
        from benchmarks import serve_bench   # python -m benchmarks.run
    except ImportError:
        import serve_bench                   # run as a script
    from repro.resilience.faults import make_faults
    from repro.serve.policies import StaticPolicy
    from repro.serve.sim import SloSpec, simulate
    from repro.serve.traffic import make_trace
    trace = make_trace(serve_bench.TRACE_SPEC,
                       duration_ms=serve_bench.SMOKE_DURATION_MS,
                       seed=serve_bench.TRACE_SEED)
    slo = SloSpec(latency_ms=serve_bench.SLO_P99_MS)
    kw = dict(slo=slo, pricer=pricer, epoch_ms=serve_bench.EPOCH_MS,
              queue_cap=serve_bench.QUEUE_CAP)
    plain = simulate(trace, StaticPolicy(rate_rps=trace.mean_rate_rps), **kw)
    empty = simulate(trace, StaticPolicy(rate_rps=trace.mean_rate_rps),
                     faults=make_faults("", duration_ms=trace.duration_ms),
                     **kw)
    return dict(
        trace_spec=serve_bench.TRACE_SPEC,
        percentiles=dict(plain.latency_ms),
        table_equal=(empty.latency_ms == plain.latency_ms
                     and empty.latencies_ms == plain.latencies_ms),
        report_equal=empty == plain)


def generate(smoke: bool = False, seed: int = TRACE_SEED) -> dict:
    """Run the chaos scenario naive vs failover, plus the determinism and
    no-fault-reproduction gates."""
    global _LAST_DOC
    from repro.resilience.failover import FailoverPolicy, RetryPolicy
    from repro.resilience.faults import make_faults
    from repro.serve.policies import StaticPolicy
    from repro.serve.sim import ServicePricer, SloSpec, SlotPlan, simulate
    from repro.serve.traffic import make_trace

    duration = SMOKE_DURATION_MS if smoke else DURATION_MS
    trace = make_trace(TRACE_SPEC, duration_ms=duration, seed=seed)
    faults = make_faults(FAULT_SPEC, duration_ms=duration)
    slo = SloSpec(latency_ms=SLO_P99_MS)
    pricer = ServicePricer()
    plan = SlotPlan(n_slots=4, point="1.00GHz@0.80V", batch_max=4)
    retry = RetryPolicy(**RETRY)
    kw = dict(slo=slo, pricer=pricer, epoch_ms=EPOCH_MS,
              queue_cap=QUEUE_CAP, faults=faults)

    naive = simulate(trace, StaticPolicy(plan=plan), **kw)
    failover = simulate(
        trace, FailoverPolicy(StaticPolicy(plan=plan),
                              headroom_slots=HEADROOM_SLOTS),
        retry=retry, **kw)
    rerun = simulate(
        trace, FailoverPolicy(StaticPolicy(plan=plan),
                              headroom_slots=HEADROOM_SLOTS),
        retry=retry, **kw)
    nofault = _nofault_reproduction(pricer)

    acceptance = dict(
        failover_completes_ge=(failover.completed_frac
                               >= naive.completed_frac),
        failover_fewer_violations=(failover.slo_violations
                                   < naive.slo_violations),
        deterministic=rerun == failover,
        nofault_table_reproduced=nofault["table_equal"])
    acceptance["ok"] = all(acceptance.values())

    doc = dict(
        scenario=dict(trace_spec=TRACE_SPEC, seed=seed,
                      duration_ms=duration, fault_spec=FAULT_SPEC,
                      slo_p99_ms=SLO_P99_MS, epoch_ms=EPOCH_MS,
                      queue_cap=QUEUE_CAP, headroom_slots=HEADROOM_SLOTS,
                      retry=dict(RETRY), n_requests=len(trace.requests)),
        policies=[_row(naive), _row(failover)],
        nofault=nofault,
        acceptance=acceptance)
    _LAST_DOC = doc
    return doc


def structured() -> dict:
    """The last generated report (for ``run.py --json``), or a smoke run."""
    return _LAST_DOC if _LAST_DOC is not None else generate(smoke=True)


def format_lines(doc: dict) -> list[str]:
    sc = doc["scenario"]
    lines = ["resilience.scenario,duration_ms,fault_spec,slo_p99_ms,"
             "n_requests",
             f"resilience.scenario,{sc['duration_ms']:.0f},"
             f"{sc['fault_spec']},{sc['slo_p99_ms']:.1f},"
             f"{sc['n_requests']}",
             "resilience.policy,completed,completed_frac,lost,retried,"
             "batches_killed,failovers,p99_ms,slo_violations,slo_met"]
    for r in doc["policies"]:
        lines.append(
            f"resilience.policy.{r['policy']},{r['completed']},"
            f"{r['completed_frac']:.4f},{r['lost']},{r['retried']},"
            f"{r['batches_killed']},{r['failovers']},{r['p99_ms']:.2f},"
            f"{r['slo_violations']},{int(r['slo_met'])}")
    a = doc["acceptance"]
    lines.append("resilience.acceptance,failover_completes_ge,"
                 "failover_fewer_violations,deterministic,"
                 "nofault_table_reproduced,ok")
    lines.append(f"resilience.acceptance,{int(a['failover_completes_ge'])},"
                 f"{int(a['failover_fewer_violations'])},"
                 f"{int(a['deterministic'])},"
                 f"{int(a['nofault_table_reproduced'])},{int(a['ok'])}")
    return lines


def run() -> list[str]:
    """CSV section for ``benchmarks/run.py``."""
    return format_lines(generate(smoke=True))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke run (same calibrated scenario)")
    ap.add_argument("--seed", type=int, default=TRACE_SEED,
                    help=f"trace seed (default {TRACE_SEED})")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="write the structured report as JSON "
                         "('-' for stdout)")
    args = ap.parse_args(argv)
    doc = generate(smoke=args.smoke, seed=args.seed)
    for line in format_lines(doc):
        print(line)
    if args.json:
        if args.json == "-":
            json.dump(doc, sys.stdout, indent=1)
            print()
        else:
            with open(args.json, "w") as f:
                json.dump(doc, f, indent=1)
            print(f"wrote {args.json}")
    if not doc["acceptance"]["ok"]:
        bad = [k for k, v in doc["acceptance"].items()
               if k != "ok" and not v]
        print(f"resilience.fail,acceptance violated: {','.join(bad)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
