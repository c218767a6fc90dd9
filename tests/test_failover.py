"""Serving failover (``serve.simulate(faults=..., retry=...)``): the
no-fault path stays bit-for-bit the healthy loop, the fault loop replays
deterministically, kill/retry/lost accounting conserves requests, the
retry policy's attempt/deadline bounds hold, ``FailoverPolicy`` headroom
rounds to valid slot counts, and an all-dead machine drains instead of
hanging.
"""

import math

import pytest

from repro import obs
from repro.resilience.failover import _slot_divisor
from repro.resilience.failover import FailoverPolicy, RetryPolicy
from repro.resilience.faults import make_faults
from repro.serve.policies import StaticPolicy
from repro.serve.sim import ServicePricer, SloSpec, SlotPlan, simulate
from repro.serve.traffic import Request, Trace, make_trace

PLAN = SlotPlan(n_slots=4, point="1.00GHz@0.80V", batch_max=1)
SLO = SloSpec(latency_ms=25.0)


def _trace(arrivals, elems=65536, kernel="softmax", duration_ms=20.0):
    """A hand-built deterministic trace — arrivals exactly where the test
    needs them (softmax@65536 services in ~1.5 ms on a 2-core slot)."""
    reqs = tuple(Request(rid=i, t_arrival_ms=float(t), kernel=kernel,
                         elems=elems)
                 for i, t in enumerate(arrivals))
    return Trace(spec="handmade", seed=0, duration_ms=duration_ms,
                 requests=reqs)


def _run(trace, faults, *, retry=None, policy=None, **kw):
    return simulate(trace, policy or StaticPolicy(plan=PLAN), slo=SLO,
                    pricer=kw.pop("pricer", None) or ServicePricer(),
                    epoch_ms=5.0, queue_cap=64, faults=faults,
                    retry=retry, **kw)


class TestRetryPolicy:

    def test_delay_is_exponential(self):
        r = RetryPolicy(base_delay_ms=0.5, backoff=2.0)
        assert [r.delay_ms(a) for a in (1, 2, 3)] == [0.5, 1.0, 2.0]

    @pytest.mark.parametrize("kw,msg", [
        (dict(max_attempts=0), "max_attempts"),
        (dict(timeout_ms=0.0), "timeout_ms"),
        (dict(backoff=0.5), "backoff"),
        (dict(base_delay_ms=-1.0), "base_delay_ms"),
    ])
    def test_validation(self, kw, msg):
        with pytest.raises(ValueError, match=msg):
            RetryPolicy(**kw)


class TestFailoverPolicy:

    def test_slot_divisor(self):
        assert _slot_divisor(8, 5) == 8
        assert _slot_divisor(8, 4) == 4
        assert _slot_divisor(8, 3) == 4
        assert _slot_divisor(8, 99) == 8
        assert _slot_divisor(6, 4) == 6
        assert _slot_divisor(8, 0) == 1

    def test_headroom_bumps_slots(self):
        trace = _trace([0.0])
        rep = _run(trace, make_faults(""), policy=FailoverPolicy(
            StaticPolicy(plan=PLAN), headroom_slots=1))
        assert rep.policy == "failover(static+1)"
        healthy = _run(trace, make_faults(""))
        # 4+1 slots rounds to 8 slots of 1 core: slower single-request
        # service than the 2-core slots the bare plan buys.
        assert rep.latency_ms["p50"] > healthy.latency_ms["p50"]

    def test_zero_headroom_is_passthrough(self):
        trace = _trace([0.0, 1.0])
        rep = _run(trace, make_faults(""), policy=FailoverPolicy(
            StaticPolicy(plan=PLAN), headroom_slots=0))
        base = _run(trace, make_faults(""))
        assert rep.latencies_ms == base.latencies_ms

    def test_negative_headroom_rejected(self):
        with pytest.raises(ValueError, match="headroom_slots"):
            FailoverPolicy(StaticPolicy(plan=PLAN), headroom_slots=-1)


class TestNoFaultPin:

    def test_empty_trace_routes_to_healthy_loop(self):
        """``faults`` without fail-stop events must not even enter the
        failover loop — the report is the healthy loop's, field for
        field.  (Window-only traces degrade the *evaluate* path, not the
        serving loop.)"""
        trace = make_trace("poisson:rate=900,kernel=softmax,elems=65536",
                           duration_ms=100.0, seed=4)
        pricer = ServicePricer()
        kw = dict(slo=SLO, pricer=pricer, epoch_ms=5.0, queue_cap=64)
        base = simulate(trace, StaticPolicy(plan=PLAN), **kw)
        for spec in ("", "throttle@5-20:isl0>0.6GHz,hbm@10-15:0.5x"):
            faulted = simulate(trace, StaticPolicy(plan=PLAN),
                               faults=make_faults(spec, duration_ms=100.0),
                               **kw)
            assert faulted == base
        assert base.n_failed == base.n_lost == base.failovers == 0

    def test_failover_loop_is_deterministic(self):
        trace = make_trace("poisson:rate=1200,kernel=softmax,elems=65536",
                           duration_ms=100.0, seed=9)
        faults = make_faults("corefail@20:c0.0,corefail@40:c0.5",
                             duration_ms=100.0)
        retry = RetryPolicy(max_attempts=3, timeout_ms=25.0)
        a = _run(trace, faults, retry=retry)
        b = _run(trace, faults, retry=retry)
        assert a == b


class TestKillAccounting:

    FAULT = "corefail@0.5:c0.0"   # lands mid-flight in the first batch

    def test_kill_then_retry_completes(self):
        trace = _trace([0.0, 0.0, 0.0, 0.0])
        rep = _run(trace, make_faults(self.FAULT, duration_ms=20.0),
                   retry=RetryPolicy(max_attempts=3, base_delay_ms=0.5))
        assert rep.n_failed == 1
        assert rep.n_retried == 1
        assert rep.n_lost == 0
        assert rep.n_completed == 4 and rep.completed_frac == 1.0
        assert rep.failovers == 1
        # The retried request paid the kill + backoff: its latency tops
        # the healthy ones.
        assert rep.max_latency_ms > 1.5 * min(rep.latencies_ms)

    def test_naive_mode_loses_killed_requests(self):
        trace = _trace([0.0, 0.0, 0.0, 0.0])
        rep = _run(trace, make_faults(self.FAULT, duration_ms=20.0),
                   retry=None)
        assert rep.n_failed == 1 and rep.n_retried == 0
        assert rep.n_lost == 1
        assert rep.n_completed == 3
        assert rep.completed_frac == pytest.approx(0.75)
        assert not rep.slo_met              # a lost request is a violation
        assert rep.slo_violations >= 1

    def test_attempt_budget_exhausts(self):
        trace = _trace([0.0, 0.0, 0.0, 0.0])
        rep = _run(trace, make_faults(self.FAULT, duration_ms=20.0),
                   retry=RetryPolicy(max_attempts=1))
        assert rep.n_retried == 0 and rep.n_lost == 1

    def test_deadline_abandons_late_retries(self):
        trace = _trace([0.0, 0.0, 0.0, 0.0])
        rep = _run(trace, make_faults(self.FAULT, duration_ms=20.0),
                   retry=RetryPolicy(max_attempts=3, timeout_ms=0.8,
                                     base_delay_ms=0.5))
        # t_retry = 0.5 + 0.5 = 1.0 > 0.8 from arrival: abandoned.
        assert rep.n_retried == 0 and rep.n_lost == 1

    def test_requests_conserved(self):
        trace = make_trace("poisson:rate=1500,kernel=softmax,elems=65536",
                           duration_ms=150.0, seed=11)
        faults = make_faults("corefail@30:c0.0,corefail@30:c0.1,"
                             "clusterfail@90:c0", duration_ms=150.0)
        rep = _run(trace, faults,
                   retry=RetryPolicy(max_attempts=2, timeout_ms=40.0))
        assert (rep.n_completed + rep.n_dropped + rep.n_shed + rep.n_lost
                == rep.n_requests)

    def test_format_lines_carries_fault_line(self):
        trace = _trace([0.0, 0.0, 0.0, 0.0])
        rep = _run(trace, make_faults(self.FAULT, duration_ms=20.0),
                   retry=None)
        txt = "\n".join(rep.format_lines())
        assert "batches_killed=1" in txt and "lost=1" in txt
        healthy = _run(trace, make_faults(""))
        assert "batches_killed" not in "\n".join(healthy.format_lines())


class TestAllDead:

    def test_cluster_death_drains_the_queue(self):
        """Killing every core must terminate the loop with everything
        after the death lost — not deadlock waiting for capacity."""
        trace = _trace([0.0, 1.0, 6.0, 7.0], duration_ms=20.0)
        faults = make_faults("clusterfail@3:c0", duration_ms=20.0)
        rep = _run(trace, faults, retry=RetryPolicy(max_attempts=3))
        assert rep.n_completed + rep.n_lost == 4
        assert rep.n_lost >= 2                # the post-death arrivals
        assert not rep.slo_met

    def test_mid_batch_cluster_death(self):
        trace = _trace([0.0] * 8, duration_ms=20.0)
        faults = make_faults("clusterfail@0.5:c0", duration_ms=20.0)
        rep = _run(trace, faults, retry=RetryPolicy(max_attempts=3))
        assert rep.n_completed == 0
        assert rep.n_lost == 8
        assert math.isnan(rep.max_latency_ms)


class TestObs:

    def test_fault_lane_and_metrics(self):
        trace = _trace([0.0, 0.0, 0.0, 0.0])
        faults = make_faults("corefail@0.5:c0.0", duration_ms=20.0)
        with obs.session(trace=True, metrics=True) as s:
            _run(trace, faults, retry=RetryPolicy(max_attempts=3))
        lanes = {e[0] for e in s.recorder.events}
        assert "resilience.faults" in lanes
        names = [e[3] for e in s.recorder.events
                 if e[0] == "resilience.faults"]
        assert names == ["corefail:c0.0"]
        m = s.metrics()
        assert m["resilience.faults.injected"]["value"] == 1
        assert m["resilience.batches_killed"]["value"] == 1
        assert m["resilience.requests_retried"]["value"] == 1
        assert m["resilience.static.completed_frac"]["value"] == 1.0
