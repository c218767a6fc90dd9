"""Manycore system-model invariants (``repro.system`` + facade wiring).

THE contract: a 1-cluster ``SystemConfig`` with unconstrained HBM reduces
*bit-for-bit* to the single-cluster ``Report`` — for every simulatable
kernel x scheduling strategy, weak and strong scaling alike.  The system
layer prices clusters through the exact same ``_price_cluster`` middle of
``api.evaluate``, so this is an identity of expression trees, not a
tolerance.  Plus: strong scaling is exactly linear for the compute-only
kernel, the shared-HBM roofline flattens the curve, the tuner's
``n_clusters`` knob sizes the part under a system power cap, the serving
pricer partitions system cores, SLO-aware admission beats tail-drop on an
overloaded trace, and ``benchmarks/run.py`` rejects unknown section names
by name.
"""

import pytest

from repro import api
from repro.cluster.scheduler import STRATEGIES
from repro.cluster.topology import SNITCH_CLUSTER
from repro.core.kernels_isa import KERNELS
from repro.system import (SystemConfig, SystemPoint, evaluate_system,
                          parse_system, select_system_point, system_cost)

#: Every numeric/structural field two Reports must agree on for
#: "bit-for-bit" (mirrors tests/test_api.py).
_REPORT_FIELDS = (
    "name", "core_points", "block", "total_blocks",
    "total_elems", "blocks_per_core", "ref_freq_ghz", "cycles_base",
    "cycles_copift", "instrs_base", "instrs_copift", "extra_contention",
    "imbalance", "dma_bound", "dma_utilization", "power_base_mw",
    "power_copift_mw")


def _assert_reports_identical(a, b):
    for f in _REPORT_FIELDS:
        assert getattr(a, f) == getattr(b, f), f


class TestSingleClusterReduction:
    """The non-negotiable invariant: Target.system(1) with unconstrained
    HBM equals the single-cluster path exactly, field by field."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("name", KERNELS)
    def test_weak_scaling_parity(self, name, strategy):
        sys_r = api.evaluate(name, api.Target.system(1, strategy=strategy),
                             blocks_per_core=3)
        one = api.evaluate(name, api.Target(strategy=strategy),
                           blocks_per_core=3)
        _assert_reports_identical(sys_r, one)

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("name", KERNELS)
    def test_strong_scaling_parity(self, name, strategy):
        sys_r = api.evaluate(name, api.Target.system(1, strategy=strategy),
                             total_blocks=48)
        one = api.evaluate(name, api.Target(strategy=strategy),
                           total_blocks=48)
        _assert_reports_identical(sys_r, one)

    def test_wide_hbm_and_zero_noc_stay_exact(self):
        """An HBM at least as wide as the private DMA and a zero-latency
        NoC must not perturb the 1-cluster numbers either (the delegation
        rule: the arbiter hands through transfer_cycles verbatim)."""
        sys_r = api.evaluate(
            "expf", api.Target.system(1, hbm_bytes_per_cycle=64.0),
            total_blocks=48)
        one = api.evaluate("expf", api.Target(), total_blocks=48)
        _assert_reports_identical(sys_r, one)


class TestSystemScaling:
    def test_compute_bound_strong_scaling_is_exactly_linear(self):
        """poly_lcg moves no bytes: 8 clusters split the same work in
        exactly 1/8 the cycles (divisible block counts, uniform cores)."""
        r1 = api.evaluate("poly_lcg", api.Target.system(1),
                          total_blocks=128)
        r8 = api.evaluate("poly_lcg", api.Target.system(8),
                          total_blocks=128)
        assert r1.cycles_copift == 8 * r8.cycles_copift
        assert r8.power_copift_mw == pytest.approx(8 * r1.power_copift_mw)

    def test_hbm_roofline_flattens_the_curve(self):
        """Behind a 16 B/cycle shared HBM the transfer floor is constant
        in cluster count (water-filling re-slices the same budget), so
        expf stops scaling once it goes memory-bound."""
        cycles = {k: api.evaluate(
            "expf", api.Target.system(k, hbm_bytes_per_cycle=16.0),
            total_blocks=128).cycles_copift for k in (1, 2, 4, 8, 16)}
        assert all(cycles[b] <= cycles[a] for a, b in
                   zip((1, 2, 4, 8), (2, 4, 8, 16)))
        assert cycles[16] == cycles[8]          # flat past the knee
        free = api.evaluate("expf", api.Target.system(16),
                            total_blocks=128).cycles_copift
        assert cycles[16] > free                # the roofline actually bit
        r = api.evaluate("expf",
                         api.Target.system(8, hbm_bytes_per_cycle=16.0),
                         total_blocks=128)
        assert r.dma_bound

    def test_report_totals_span_the_system(self):
        r = api.evaluate("expf", api.Target.system(4), blocks_per_core=2)
        assert r.n_cores == 4 * SNITCH_CLUSTER.n_cores
        assert len(r.core_points) == r.n_cores
        assert len(r.blocks_per_core) == r.n_cores
        assert r.total_blocks == 2 * r.n_cores

    def test_plan_transformed_evaluation_rejected(self):
        with pytest.raises(ValueError, match="single-cluster"):
            api.evaluate("expf", api.Target.system(2), plan=object())

    def test_needs_at_least_one_block(self):
        with pytest.raises(ValueError):
            api.evaluate("expf", api.Target.system(2), total_blocks=0)

    def test_evaluate_system_needs_a_system_config(self):
        with pytest.raises(ValueError, match="no SystemConfig"):
            evaluate_system("expf", api.Target())


class TestTopologyAndGrammar:
    def test_defaults_are_the_lone_cluster(self):
        s = SystemConfig()
        assert s.n_clusters == 1 and s.n_cores == SNITCH_CLUSTER.n_cores
        assert s.is_uniform
        assert s.hbm_bytes_per_cycle is None

    def test_validation(self):
        with pytest.raises(ValueError):
            SystemConfig(clusters=())
        with pytest.raises(TypeError):
            SystemConfig(clusters=("not a cluster",))
        with pytest.raises(ValueError):
            SystemConfig(hbm_bytes_per_cycle=0.0)
        with pytest.raises(ValueError):
            SystemConfig(noc_latency_cycles=-1)
        with pytest.raises(ValueError):
            SystemConfig(cluster_strategy="no_such_strategy")

    def test_parse_system_round_trip(self):
        s = parse_system("4x8c,hbm=256,noc=12,strategy=lpt", SNITCH_CLUSTER)
        assert s.n_clusters == 4
        assert s.clusters[0].n_cores == 8
        assert s.hbm_bytes_per_cycle == 256.0
        assert s.noc_latency_cycles == 12
        assert s.cluster_strategy == "lpt"
        assert parse_system("2x8c,hbm=none",
                            SNITCH_CLUSTER).hbm_bytes_per_cycle is None

    @pytest.mark.parametrize("bad", [
        "", "4", "4x", "x8c", "0x8c", "4x0c", "4x8", "4x8c,hbm",
        "4x8c,hbm=-2", "4x8c,noc=1.5", "4x8c,strategy=nope",
        "4x8c,bogus=1"])
    def test_parse_system_grammar_errors(self, bad):
        with pytest.raises(ValueError):
            parse_system(bad, SNITCH_CLUSTER)


class TestTargetSystem:
    def test_from_int_str_and_config(self):
        by_int = api.Target.system(4, hbm_bytes_per_cycle=256.0)
        by_str = api.Target.system("4x8c,hbm=256")
        by_cfg = api.Target.system(SystemConfig.homogeneous(
            4, SNITCH_CLUSTER, hbm_bytes_per_cycle=256.0))
        assert by_int.system_config == by_str.system_config \
            == by_cfg.system_config
        assert by_int.n_clusters == 4
        assert by_int.n_cores == 32
        assert len(by_int.core_points) == 32

    def test_cluster_must_match_the_system(self):
        sys_cfg = SystemConfig.homogeneous(2, SNITCH_CLUSTER)
        with pytest.raises(ValueError, match="first cluster"):
            api.Target(cluster=SNITCH_CLUSTER.with_cores(4),
                       system_config=sys_cfg)

    def test_exported_from_api(self):
        assert api.SystemConfig is SystemConfig
        assert api.parse_system is parse_system


class TestTunerClusterCount:
    def test_system_point_under_power_cap(self):
        tuner = api.Tuner(api.Target.homogeneous(power_cap_mw=4000.0))
        res = tuner.operating_point("softmax", n_clusters=4)
        assert isinstance(res, SystemPoint)
        assert 1 <= res.n_clusters <= 4
        assert res.feasible
        assert res.best_cost.power_mw <= 4000.0

    def test_time_objective_buys_clusters(self):
        tuner = api.Tuner()
        res = tuner.operating_point("softmax", n_clusters=(1, 2, 4),
                                    objective="time")
        assert res.n_clusters == 4   # more clusters = faster, uncapped

    def test_energy_objective_stays_small(self):
        """Uncapped energy: extra clusters only add power for the same
        work, so the selection keeps the part at one cluster."""
        tuner = api.Tuner()
        res = tuner.operating_point("softmax", n_clusters=(1, 2, 4))
        assert res.n_clusters == 1

    def test_simulatable_kernel_priced_through_evaluate(self):
        est = system_cost("expf", SystemConfig.homogeneous(2,
                                                           SNITCH_CLUSTER),
                          SNITCH_CLUSTER.nominal.name)
        assert est.cycles > 0 and est.power_mw > 0 and est.feasible


class TestServeSystem:
    def test_pricer_partitions_system_cores(self):
        from repro.serve.sim import ServicePricer
        pricer = ServicePricer(system=SystemConfig.homogeneous(
            4, SNITCH_CLUSTER))
        assert pricer.n_cores == 32

    def test_nonuniform_system_rejected(self):
        from repro.serve.sim import ServicePricer
        mixed = SystemConfig(clusters=(SNITCH_CLUSTER,
                                       SNITCH_CLUSTER.with_cores(4)))
        with pytest.raises(ValueError, match="uniform"):
            ServicePricer(system=mixed)

    def test_multi_cluster_slot_prices_via_target_system(self):
        """A slot spanning k whole clusters prices exactly what the
        facade prices on the equivalent Target.system; a sub-cluster
        slot is bit-for-bit the single-cluster pricer."""
        from repro.serve.sim import ServicePricer
        system = SystemConfig.homogeneous(4, SNITCH_CLUSTER)
        pricer = ServicePricer(system=system)
        single = ServicePricer()
        pt = SNITCH_CLUSTER.nominal.name
        est = pricer.price("expf", 65536, 16, pt)
        assert est.cycles == system_cost(
            "expf", SystemConfig.homogeneous(2, SNITCH_CLUSTER), pt,
            problem=65536).cycles
        assert pricer.price("expf", 65536, 4, pt) \
            == single.price("expf", 65536, 4, pt)

    def test_simulate_runs_on_a_system_pricer(self):
        from repro.serve.policies import StaticPolicy
        from repro.serve.sim import ServicePricer, simulate
        from repro.serve.traffic import make_trace
        pricer = ServicePricer(system=SystemConfig.homogeneous(
            2, SNITCH_CLUSTER))
        tr = make_trace("poisson:rate=400,kernel=softmax,elems=65536",
                        duration_ms=300.0, seed=3)
        rep = simulate(tr, StaticPolicy(rate_rps=tr.mean_rate_rps),
                       pricer=pricer)
        assert rep.n_completed + rep.n_dropped == rep.n_requests


class TestSloAwareAdmission:
    def test_sheds_beat_tail_drop_on_overload(self):
        """Satellite acceptance: on a trace past the plan's capacity the
        SLO-aware gate sheds early and keeps admitted requests within the
        bound — strictly fewer total violations than tail-drop, which
        poisons the queue and lets nearly every completion run late."""
        from repro.serve.policies import StaticPolicy
        from repro.serve.sim import ServicePricer, SloSpec, SlotPlan, simulate
        from repro.serve.traffic import make_trace
        pricer = ServicePricer()
        plan = SlotPlan(n_slots=1, point="0.50GHz@0.60V", batch_max=1)
        tr = make_trace("poisson:rate=1500,kernel=softmax,elems=65536",
                        duration_ms=1000.0, seed=7)
        slo = SloSpec(latency_ms=5.0)
        tail = simulate(tr, StaticPolicy(plan=plan), slo=slo, pricer=pricer,
                        queue_cap=64)
        shed = simulate(tr, StaticPolicy(plan=plan), slo=slo, pricer=pricer,
                        queue_cap=64, admission="slo_aware")
        assert shed.n_shed > 0
        assert tail.n_shed == 0
        assert shed.slo_violations < tail.slo_violations
        # The gate's point: what it admits, it serves within the bound.
        assert shed.latency_ms["p99"] <= slo.latency_ms
        assert tail.latency_ms["p99"] > slo.latency_ms

    def test_admission_validation(self):
        from repro.serve.policies import StaticPolicy
        from repro.serve.sim import SloSpec, simulate
        from repro.serve.traffic import make_trace
        tr = make_trace("poisson:rate=50,kernel=softmax,elems=4096",
                        duration_ms=100.0, seed=1)
        with pytest.raises(ValueError, match="admission"):
            simulate(tr, StaticPolicy(rate_rps=50.0),
                     slo=SloSpec(latency_ms=5.0), admission="bogus")
        with pytest.raises(ValueError, match="SloSpec"):
            simulate(tr, StaticPolicy(rate_rps=50.0),
                     admission="slo_aware")


class TestRunHarness:
    def test_structured_rejects_unknown_section_by_name(self):
        from benchmarks.run import _structured
        with pytest.raises(ValueError, match="unknown section 'nope'"):
            _structured("nope")

    def test_structured_known_sections(self):
        from benchmarks.run import _structured
        doc = _structured("system")
        assert doc["acceptance"]["ok"]
        assert _structured("table1") is None   # known, no payload

    def test_system_bench_smoke_contract(self):
        from benchmarks.system_bench import format_lines, generate
        doc = generate(smoke=True)
        assert doc["acceptance"]["ok"]
        effs = doc["scaling_efficiency"]
        assert all(e >= 0.9 for curve in effs.values() for e in curve)
        lines = format_lines(doc)
        assert any(line.startswith("system.acceptance") for line in lines)
