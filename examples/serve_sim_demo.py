"""Serving-simulator walkthrough: traffic in, SLOs out.

Generates a bursty arrival trace, runs the three autoscaling policies
(static / reactive / model-predictive) over the discrete-event simulator,
and prints the latency percentiles, the energy split (active vs idle
leakage) and whether each policy met a p99 <= 10 ms SLO — then asks the
tuner the same question directly via the latency-constrained objective.

Run:  PYTHONPATH=src python examples/serve_sim_demo.py
"""

from repro.api import Tuner
from repro.serve.policies import POLICIES
from repro.serve.sim import ServicePricer, SloSpec, simulate
from repro.serve.traffic import make_trace

SPEC = ("bursty:rate=860,burst=2.33,period_ms=1200,duty=0.22,"
        "kernel=softmax,elems=65536")


def main():
    trace = make_trace(SPEC, duration_ms=2400.0, seed=11)
    slo = SloSpec(latency_ms=10.0)
    print(f"trace {SPEC!r}")
    print(f"  {trace.n_requests} requests over {trace.duration_ms:.0f} ms "
          f"(mean {trace.mean_rate_rps:.0f} req/s), SLO p99 <= "
          f"{slo.latency_ms:g} ms\n")

    pricer = ServicePricer()
    reports = {}
    for name, factory in POLICIES.items():
        reports[name] = simulate(trace, factory(trace.mean_rate_rps),
                                 slo=slo, pricer=pricer, epoch_ms=10.0,
                                 queue_cap=256)

    print(f"{'policy':10s} {'p50':>8s} {'p99':>8s} {'max':>8s} "
          f"{'energy':>10s} {'idle':>9s} {'switches':>8s}  slo")
    for name, r in reports.items():
        print(f"{name:10s} {r.latency_ms['p50']:7.2f}m "
              f"{r.latency_ms['p99']:7.2f}m {r.max_latency_ms:7.2f}m "
              f"{r.energy_uj:8.0f}uJ {r.idle_energy_uj:7.0f}uJ "
              f"{r.plan_switches:8d}  "
              f"{'MET' if r.slo_met else 'MISSED'}")

    s, m = reports["static"], reports["mpc"]
    print(f"\nmpc vs static: p99 {s.latency_ms['p99']:.1f} -> "
          f"{m.latency_ms['p99']:.1f} ms at "
          f"{100 * (1 - m.energy_uj / s.energy_uj):.1f}% less energy — "
          f"latency bought back from the idle-tier leakage static pays "
          f"all trough long.")

    # The same question at the single-batch level, straight to the tuner:
    # minimum-energy operating point finishing softmax within 5 ms.
    res = Tuner().operating_point("softmax", latency_ns=5e6)
    c = res.best_cost
    print(f"\ntuner: 'energy@time<=5ms' on softmax -> "
          f"{res.best.n_cores} cores @ {res.best.point}: "
          f"{c.time_ns / 1e6:.3f} ms, {c.energy_pj / 1e6:.1f} uJ")


if __name__ == "__main__":
    main()
