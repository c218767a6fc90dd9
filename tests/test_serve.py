"""The serving layer: the three ServeEngine decode-path regressions
(each pinned failing-before/passing-after), the tuner's
latency-constrained objective, and the discrete-event serving simulator
(trace determinism, the 1-core/1-request reduction to ``api.evaluate``,
policies, and the benchmark's acceptance inequality)."""

import math
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.serve import (POLICIES, ModelPredictivePolicy, PolicyContext,
                         ReactivePolicy, Request, ServicePricer, SimReport,
                         SloSpec, SlotPlan, StaticPolicy, Trace, make_trace,
                         plan_for_rate, simulate)
from repro.serve.engine import ServeEngine, _mix32, make_cache


def _engine(**kw):
    """The jit traces resolve lazily, so an engine over a placeholder
    config exercises every decode-path guard without building a model."""
    kw.setdefault("batch", 2)
    kw.setdefault("max_len", 32)
    return ServeEngine(object(), None, **kw)


class TestEngineZeroSteps:
    def test_n_steps_zero_returns_exactly_the_prompt(self):
        # Regression: generate(n_steps=0) used to emit one sampled token
        # anyway (the decode loop ran once before checking).
        eng = _engine()
        prompts = np.arange(8, dtype=np.int32).reshape(2, 4)
        res = eng.generate(prompts, 0)
        assert res.steps == 0
        assert res.tokens.shape == (2, 4)
        np.testing.assert_array_equal(res.tokens, prompts)

    def test_bad_batch_dim_is_a_valueerror_naming_the_dimension(self):
        # Regression: this was a bare `assert`, gone under python -O and
        # naming nothing.
        eng = _engine(batch=2)
        with pytest.raises(ValueError, match=r"batch dimension is 3"):
            eng.generate(np.zeros((3, 4), np.int32), 0)
        with pytest.raises(ValueError, match=r"batch=2"):
            eng.generate(np.zeros((3, 4), np.int32), 0)

    def test_negative_steps_and_overlong_decode_are_valueerrors(self):
        eng = _engine(max_len=16)
        with pytest.raises(ValueError, match=r"n_steps=-1"):
            eng.generate(np.zeros((2, 4), np.int32), -1)
        with pytest.raises(ValueError, match=r"max_len=16"):
            eng.generate(np.zeros((2, 10), np.int32), 7)


class TestEngineDonation:
    def test_generate_donates_the_cache_in_place(self):
        """``generate`` donates the cache to the prefill and every step:
        the gauge reads 1, no donation falls back, and both compiled
        programs alias their cache input to their cache output under the
        names the device-trace readers key on."""
        from repro import obs
        from repro.configs import load_config
        from repro.models.model import init_params

        cfg = load_config("olmo-1b", "smoke")
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, max_len=16, batch=2)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with obs.session(trace=False, metrics=True) as sess:
                eng.generate(np.ones((2, 4), np.int32), 3)
        assert not [w for w in caught if "donated" in str(w.message)]
        assert sess.metrics()["serve.cache.donated"]["value"] == 1

        cache = jax.eval_shape(lambda: make_cache(cfg, 2, 16))
        tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        programs = {
            "jit_serve_step": eng._step.lower(params, cache, tok, jnp.int32(4)),
            "jit_prefill": eng._prefill.lower(
                params, cache, jax.ShapeDtypeStruct((2, 4), jnp.int32))}
        n_params, n_cache = (len(jax.tree.leaves(t)) for t in (params, cache))
        # outputs (logits, *cache); inputs (*params, *cache, ...)
        want = {(1 + i, n_params + i) for i in range(n_cache)}
        for name, lowered in programs.items():
            text = lowered.compile().as_text()
            assert text.startswith(f"HloModule {name},")
            aliases = re.search(r"input_output_alias=\{(.*?) \}, ", text)
            got = {(int(o), int(i)) for o, i in re.findall(
                r"\{(\d+)\}: \((\d+), \{\}", aliases.group(1))}
            assert got == want, (name, got)


class TestEngineTunedDefaultScope:
    def test_autotune_restores_process_default_on_close(self):
        # Regression: autotune=True flipped kops.set_tuned_defaults(True)
        # for the whole process and nothing ever undid it.
        prev = kops.tuned_defaults_enabled()
        try:
            eng = _engine(autotune=True)
            assert kops.tuned_defaults_enabled() is True
            eng.close()
            assert kops.tuned_defaults_enabled() == prev
            eng.close()   # idempotent
            assert kops.tuned_defaults_enabled() == prev
        finally:
            kops.set_tuned_defaults(prev)

    def test_context_manager_scopes_the_flip(self):
        prev = kops.tuned_defaults_enabled()
        try:
            with _engine(autotune=True) as eng:
                assert eng.operating_plan is not None
                assert kops.tuned_defaults_enabled() is True
            assert kops.tuned_defaults_enabled() == prev
        finally:
            kops.set_tuned_defaults(prev)

    def test_persist_escape_hatch_survives_close(self):
        prev = kops.tuned_defaults_enabled()
        try:
            eng = _engine(autotune=True, persist_tuned_defaults=True)
            eng.close()
            assert kops.tuned_defaults_enabled() is True
        finally:
            kops.set_tuned_defaults(prev)

    def test_close_without_autotune_is_a_noop(self):
        prev = kops.tuned_defaults_enabled()
        eng = _engine()
        eng.close()
        assert kops.tuned_defaults_enabled() == prev


class TestEngineSampling:
    def test_slots_draw_from_distinct_streams(self):
        # Regression: temperature sampling seeded kops.uniform with
        # `seed + step` for the WHOLE batch — every slot (and every
        # engine sharing a seed) drew the identical noise row.
        eng = _engine(temperature=1.0, seed=7)
        prompts = np.zeros((2, 4), np.int32)   # identical rows
        seeds = eng._slot_seeds(prompts)
        assert len(set(seeds)) == 2
        u0 = np.asarray(kops.uniform(_mix32(seeds[0], 0), (64,)))
        u1 = np.asarray(kops.uniform(_mix32(seeds[1], 0), (64,)))
        assert not np.array_equal(u0, u1)

    def test_streams_distinct_across_slots_steps_and_prompts(self):
        eng = _engine(temperature=1.0, seed=3)
        a = eng._slot_seeds(np.zeros((2, 4), np.int32))
        b = eng._slot_seeds(np.ones((2, 4), np.int32))
        grid = {_mix32(s, step) for s in a + b for step in range(8)}
        assert len(grid) == 4 * 8   # no (slot, prompt, step) collisions

    def test_sampling_is_deterministic_per_stream(self):
        eng = _engine(temperature=1.0, seed=7)
        seeds = eng._slot_seeds(np.zeros((2, 4), np.int32))
        logits = jnp.zeros((2, 64))
        t1 = np.asarray(eng._sample(logits, 0, seeds))
        t2 = np.asarray(eng._sample(logits, 0, seeds))
        np.testing.assert_array_equal(t1, t2)
        assert not np.array_equal(t1, np.asarray(eng._sample(logits, 1,
                                                             seeds)))


class TestLatencyObjective:
    def test_parse_objective_grammar(self):
        from repro.tune.cost import parse_objective
        assert parse_objective("energy") == ("energy", None)
        assert parse_objective("energy@time<=2.5ms") == ("energy", 2.5e6)
        assert parse_objective("cycles@time<=3us") == ("cycles", 3e3)
        assert parse_objective("time@time<=1s") == ("time", 1e9)
        assert parse_objective("edp@time<=500")[1] == 500.0   # bare = ns

    def test_parse_objective_rejects_malformed_bounds(self):
        from repro.tune.cost import parse_objective
        with pytest.raises(ValueError, match="unknown objective"):
            parse_objective("watts")
        with pytest.raises(ValueError, match="bad latency bound"):
            parse_objective("energy@cycles<=5")
        with pytest.raises(ValueError, match="bad latency bound"):
            parse_objective("energy@time<=fast")
        with pytest.raises(ValueError, match="must be positive"):
            parse_objective("energy@time<=-3ms")

    def test_constrain_latency_round_trips(self):
        from repro.tune.cost import constrain_latency, parse_objective
        obj = constrain_latency("energy", 2.5e6)
        assert parse_objective(obj) == ("energy", 2.5e6)

    def test_violators_rank_after_every_meeting_candidate_by_speed(self):
        from repro.tune.cost import (CostEstimate, meets_latency,
                                     objective_value)

        def est(t, e):
            return CostEstimate(cycles=1, time_ns=t, energy_pj=e, ipc=1.0,
                                power_mw=1.0, feasible=True,
                                dma_bound=False)

        obj = "energy@time<=100ns"
        ok_cheap, ok_rich = est(90.0, 5.0), est(50.0, 9.0)
        slow, slower = est(120.0, 1.0), est(300.0, 0.5)
        vals = [objective_value(e, obj)
                for e in (ok_cheap, ok_rich, slow, slower)]
        assert vals[0] < vals[1] < vals[2] < vals[3]
        assert meets_latency(ok_cheap, obj)
        assert not meets_latency(slow, obj)
        assert meets_latency(slow, "energy")   # vacuous without a bound

    def test_tuner_operating_point_honors_latency_bound(self):
        from repro.api import Tuner
        free = Tuner().operating_point("softmax")
        bound = free.best_cost.time_ns * 0.8
        capped = Tuner().operating_point("softmax", latency_ns=bound)
        assert capped.best_cost.time_ns <= bound
        assert capped.best_cost.energy_pj >= free.best_cost.energy_pj

    def test_tuner_plan_latency_bound_composes(self):
        from repro.api import Tuner
        free = Tuner().plan("softmax")
        generous = Tuner().plan("softmax",
                                latency_ns=free.best_cost.time_ns * 10)
        assert generous.best == free.best


class TestTraffic:
    def test_same_spec_and_seed_replay_identically(self):
        a = make_trace("poisson:rate=500", duration_ms=200.0, seed=9)
        b = make_trace("poisson:rate=500", duration_ms=200.0, seed=9)
        assert a.requests == b.requests
        c = make_trace("poisson:rate=500", duration_ms=200.0, seed=10)
        assert a.requests != c.requests

    def test_request_shape_keys_apply(self):
        tr = make_trace("poisson:rate=800,kernel=expf,elems=4096",
                        duration_ms=100.0, seed=1)
        assert tr.n_requests > 0
        assert all(r.kernel == "expf" and r.elems == 4096
                   for r in tr.requests)

    def test_bursty_concentrates_arrivals_in_the_duty_window(self):
        tr = make_trace("bursty:rate=200,burst=8,period_ms=100,duty=0.2",
                        duration_ms=1000.0, seed=4)
        in_burst = sum((r.t_arrival_ms % 100.0) < 20.0 for r in tr.requests)
        assert in_burst > tr.n_requests / 2   # 20% of time, >50% of load

    def test_spec_grammar_errors(self):
        with pytest.raises(ValueError, match="unknown trace family"):
            make_trace("pareto:rate=5")
        with pytest.raises(ValueError, match="bad trace-spec token"):
            make_trace("poisson:rate")
        with pytest.raises(ValueError, match="missing required"):
            make_trace("poisson:kernel=softmax")
        with pytest.raises(ValueError, match="unknown trace-spec keys"):
            make_trace("poisson:rate=5,ratee=6")
        with pytest.raises(ValueError, match="duty"):
            make_trace("bursty:rate=5,duty=1.5")
        with pytest.raises(ValueError, match="low <= high"):
            make_trace("diurnal:low=9,high=3")
        with pytest.raises(ValueError, match="duration_ms"):
            make_trace("poisson:rate=5", duration_ms=0.0)


class TestSimulator:
    def test_percentile_table_is_bit_reproducible(self):
        trace = make_trace("bursty:rate=600,kernel=softmax,elems=16384",
                           duration_ms=400.0, seed=2)
        slo = SloSpec(latency_ms=10.0)
        pricer = ServicePricer()
        a = simulate(trace, ModelPredictivePolicy(), slo=slo, pricer=pricer,
                     epoch_ms=10.0)
        b = simulate(trace, ModelPredictivePolicy(), slo=slo, pricer=pricer,
                     epoch_ms=10.0)
        assert a.latencies_ms == b.latencies_ms
        assert a.latency_ms == b.latency_ms
        assert a.energy_uj == b.energy_uj
        assert a.plan_switches == b.plan_switches

    def test_one_core_one_request_reduces_to_api_evaluate(self):
        # A single request at t=0 on a 1-core slot must cost EXACTLY the
        # Report's cycles at the slot's operating point — the simulator
        # adds queueing around api.evaluate, never noise inside it.
        from repro.api import SNITCH_CLUSTER, Target, evaluate
        from repro.api.registry import kernel
        elems = 8192
        point = "1.00GHz@0.80V"
        trace = Trace(spec="manual", seed=0, duration_ms=1.0,
                      requests=(Request(0, 0.0, "expf", elems),))
        plan = SlotPlan(n_slots=8, point=point, batch_max=1)
        rep = simulate(trace, StaticPolicy(plan=plan),
                       slo=SloSpec(latency_ms=100.0))
        blocks = -(-elems // kernel("expf").get_workload().max_block)
        ref = evaluate("expf", Target.homogeneous(
            n_cores=1, point=SNITCH_CLUSTER.point(point)),
            total_blocks=blocks)
        assert rep.n_completed == 1
        assert rep.latencies_ms[0] == \
            ref.cycles_copift / ref.ref_freq_ghz * 1e-6
        assert rep.active_energy_uj == pytest.approx(
            ref.power_copift_mw * ref.cycles_copift / ref.ref_freq_ghz
            * 1e-6)

    def test_queue_cap_drops_break_the_slo(self):
        trace = make_trace("poisson:rate=4000,elems=65536",
                           duration_ms=100.0, seed=5)
        plan = SlotPlan(n_slots=1, point="0.50GHz@0.60V", batch_max=1)
        rep = simulate(trace, StaticPolicy(plan=plan),
                       slo=SloSpec(latency_ms=1000.0), queue_cap=2)
        assert rep.n_dropped > 0
        assert not rep.slo_met   # dropped = infinite latency

    def test_empty_trace_yields_empty_report(self):
        trace = Trace(spec="manual", seed=0, duration_ms=10.0, requests=())
        rep = simulate(trace, StaticPolicy(
            plan=SlotPlan(n_slots=1, point="0.50GHz@0.60V")))
        assert rep.n_completed == 0 and rep.energy_uj == 0.0
        assert math.isnan(rep.latency_ms["p99"])
        assert rep.slo_met   # vacuous: no SLO given

    def test_validation_errors(self):
        trace = make_trace("poisson:rate=100", duration_ms=10.0, seed=0)
        pol = StaticPolicy(plan=SlotPlan(n_slots=1, point="0.50GHz@0.60V"))
        with pytest.raises(ValueError, match="epoch_ms"):
            simulate(trace, pol, epoch_ms=0.0)
        with pytest.raises(ValueError, match="queue_cap"):
            simulate(trace, pol, queue_cap=0)
        with pytest.raises(ValueError, match="does not divide"):
            SlotPlan(n_slots=3, point="0.50GHz@0.60V").validate(8)
        with pytest.raises(ValueError, match="n_slots"):
            SlotPlan(n_slots=0, point="0.50GHz@0.60V").validate(8)
        with pytest.raises(ValueError, match="batch_max"):
            SlotPlan(n_slots=1, point="0.50GHz@0.60V",
                     batch_max=0).validate(8)
        with pytest.raises(ValueError, match="latency_ms"):
            SloSpec(latency_ms=0.0)
        with pytest.raises(ValueError, match="percentile"):
            SloSpec(latency_ms=1.0, percentile=0.0)

    def test_sim_emits_obs_metrics(self):
        from repro import obs
        trace = make_trace("poisson:rate=300", duration_ms=50.0, seed=1)
        pol = StaticPolicy(plan=SlotPlan(n_slots=4, point="0.75GHz@0.70V"))
        with obs.session(trace=False, metrics=True) as sess:
            simulate(trace, pol, slo=SloSpec(latency_ms=50.0))
        m = sess.metrics()
        assert "serve.sim.static.p99_ms" in m
        assert "serve.sim.static.energy_uj" in m


class TestPolicies:
    def _ctx(self, slo_ms=10.0):
        return PolicyContext(pricer=ServicePricer(), kernel="softmax",
                             elems=16384, n_cores=8, epoch_ms=10.0,
                             slo=SloSpec(latency_ms=slo_ms),
                             power_cap_mw=None)

    def test_plan_for_rate_scales_energy_with_load(self):
        ctx = self._ctx()
        lo, hi = plan_for_rate(ctx, 50.0), plan_for_rate(ctx, 3000.0)
        p = ctx.pricer

        def per_req(plan):
            est = p.price(ctx.kernel, ctx.elems * plan.batch_max,
                          plan.cores_per_slot(ctx.n_cores), plan.point)
            cap = plan.n_slots * plan.batch_max / (est.time_ns * 1e-9)
            return est.energy_pj / plan.batch_max, cap

        e_lo, cap_lo = per_req(lo)
        e_hi, cap_hi = per_req(hi)
        assert cap_lo >= 1.25 * 50.0 and cap_hi >= 1.25 * 3000.0
        assert e_lo <= e_hi   # light load buys the cheaper tier

    def test_plan_for_rate_respects_power_cap(self):
        ctx = PolicyContext(pricer=ServicePricer(), kernel="softmax",
                            elems=16384, n_cores=8, epoch_ms=10.0,
                            slo=SloSpec(latency_ms=10.0),
                            power_cap_mw=100.0)
        plan = plan_for_rate(ctx, 200.0)
        est = ctx.pricer.price(ctx.kernel, ctx.elems * plan.batch_max,
                               plan.cores_per_slot(8), plan.point)
        assert plan.n_slots * est.power_mw <= 100.0

    def test_policy_constructor_validation(self):
        with pytest.raises(ValueError, match="exactly one"):
            StaticPolicy()
        with pytest.raises(ValueError, match="exactly one"):
            StaticPolicy(plan=SlotPlan(n_slots=1, point="x"),
                         rate_rps=10.0)
        with pytest.raises(ValueError, match="lo_queue < hi_queue"):
            ReactivePolicy(hi_queue=4, lo_queue=4)
        with pytest.raises(ValueError, match="alpha"):
            ModelPredictivePolicy(alpha=0.0)

    def test_policies_table_is_complete(self):
        assert set(POLICIES) == {"static", "reactive", "mpc"}
        for factory in POLICIES.values():
            assert factory(100.0).name in POLICIES


class TestServeBenchAcceptance:
    def test_mpc_meets_the_slo_static_misses_at_lower_energy(self):
        # The PR's acceptance inequality, on the benchmark's own smoke
        # scenario: static (provisioned for the mean rate) misses the
        # p99 SLO the bursty trace sets up, mpc meets it, and mpc's
        # total energy (active + idle leakage) is no worse.
        from benchmarks import serve_bench
        doc = serve_bench.generate(smoke=True)
        acc = doc["acceptance"]
        assert acc["static_missed"]
        assert acc["mpc_met"]
        assert acc["mpc_energy_le_static"]
        assert acc["deterministic"]
        assert acc["ok"]
