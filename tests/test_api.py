"""Facade invariants (``repro.api``).

THE contract: the facade's single ``evaluate`` code path reproduces the
paper-calibrated numbers *bit-for-bit* — ``Target.single_pe()`` equals
the single-PE machinery, every scheduling strategy collapses onto
block-cyclic on uniform cores, and the historical result classes are
aliases of the one ``Report``.  (The pre-facade shims were deleted after
PR 8; their parity contracts live on as facade-internal invariants here
and as the 1-cluster system reduction in ``tests/test_system_model.py``.)
Plus: the registry resolves every historical name, ``config`` overrides
are scoped and race-free, the ``Tuner`` shares one cache across its
methods, and per-island block tuning never scores worse than the
shared-block plan under the same power cap.
"""

import importlib
import inspect
import threading
import warnings

import pytest

from repro import api
from repro.cluster.scheduler import STRATEGIES
from repro.core.analytics import TABLE_I
from repro.core.energy import evaluate_energy
from repro.core.kernels_isa import KERNELS, baseline_trace, copift_schedule
from repro.core.timing import evaluate_kernel
from repro.kernels import ops as kops
from repro.serve.engine import ServeEngine

#: Every numeric/structural field of a Report two evaluations must agree
#: on for "bit-for-bit" (``strategy`` is a label, compared separately).
_REPORT_FIELDS = (
    "name", "core_points", "block", "total_blocks",
    "total_elems", "blocks_per_core", "ref_freq_ghz", "cycles_base",
    "cycles_copift", "instrs_base", "instrs_copift", "extra_contention",
    "imbalance", "dma_bound", "dma_utilization", "power_base_mw",
    "power_copift_mw")


def _assert_reports_identical(a, b):
    for f in _REPORT_FIELDS:
        assert getattr(a, f) == getattr(b, f), f


class TestSinglePeReduction:
    """Target.single_pe() is the paper's setting: the facade must equal
    the calibrated single-PE machinery exactly (the independent ground
    truth, not merely the old cluster code)."""

    @pytest.mark.parametrize("name", KERNELS)
    def test_single_pe_bit_for_bit(self, name):
        pe = evaluate_kernel(name, baseline_trace(name),
                             copift_schedule(name), TABLE_I[name].max_block)
        r = api.evaluate(name, api.Target.single_pe())
        assert r.speedup == pe.speedup
        assert r.ipc_copift == pe.ipc_copift
        assert r.ipc_base == pe.ipc_base
        assert r.cycles_copift == pe.cycles_copift
        assert r.cycles_base == pe.cycles_base
        en = evaluate_energy(name)
        assert r.energy_saving == en.energy_saving
        assert r.power_ratio == en.power_ratio
        assert r.extra_contention == 0.0

    def test_homogeneous_cycles_are_exact_ints(self):
        r = api.evaluate("expf", api.Target.homogeneous(n_cores=8))
        assert isinstance(r.cycles_copift, int)
        assert isinstance(r.cycles_base, int)


class TestFacadeParity:
    """The one evaluate path is internally consistent bit-for-bit: on
    uniform cores every weighted strategy collapses onto block-cyclic,
    and the constructors that claim equivalence deliver it exactly."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("name", KERNELS)
    def test_uniform_cores_strategy_invariant(self, name, strategy):
        r = api.evaluate(
            name, api.Target.homogeneous(n_cores=8).with_strategy(strategy))
        base = api.evaluate(name, api.Target.homogeneous(n_cores=8))
        _assert_reports_identical(r, base)

    @pytest.mark.parametrize("name", KERNELS)
    def test_single_pe_is_the_one_core_homogeneous_target(self, name):
        r = api.evaluate(name, api.Target.single_pe())
        base = api.evaluate(name, api.Target.homogeneous(n_cores=1))
        _assert_reports_identical(r, base)

    def test_result_classes_are_report_aliases(self):
        from repro.cluster import ClusterKernelResult, HetClusterResult
        assert ClusterKernelResult is api.Report
        assert HetClusterResult is api.Report

    def test_metric_properties_defined_once(self):
        """The drift-prone copy-pasted properties are gone: both historical
        classes resolve every metric from the shared mixin."""
        for prop in ("speedup", "ipc_base", "ipc_copift", "power_ratio",
                     "energy_saving", "time_us", "cycles_per_elem",
                     "energy_pj_per_elem"):
            assert getattr(api.Report, prop) is getattr(api.ReportMetrics,
                                                        prop)


class TestShimsGone:
    """Removed names stay removed: the pre-facade shims, the kernels'
    process-wide setters and the tuned-tiling keywords (importing one is
    an error, passing one a ``TypeError``, neither a warning)."""

    def test_cluster_shims_removed(self):
        import repro.cluster as cluster
        for name in ("evaluate_cluster", "evaluate_cluster_het"):
            assert not hasattr(cluster, name)
            assert name not in cluster.__all__

    def test_kernel_setter_shims_removed(self):
        import repro.kernels as kernels
        for name in ("set_default_impl", "enable_tuned_defaults"):
            assert not hasattr(kops, name)
            assert not hasattr(kernels, name)
            assert name not in kernels.__all__

    @pytest.mark.parametrize("module", ["repro.kernels", "repro.kernels.ops"])
    @pytest.mark.parametrize("name", [
        "set_tuned_defaults", "tuned_defaults_enabled", "set_impl"])
    def test_kernel_process_setters_removed(self, module, name):
        """The kernel tiling is the kernel module's constant and the impl
        is set only in a scope: no process-wide setter is left."""
        mod = importlib.import_module(module)
        assert not hasattr(mod, name)
        assert name not in getattr(mod, "__all__", ())

    @pytest.mark.parametrize("build", [
        lambda: ServeEngine(object(), None, autotune=True),
        lambda: ServeEngine(object(), None, power_cap_mw=250.0),
        lambda: ServeEngine(object(), None, persist_tuned_defaults=True),
        lambda: ServeEngine(object(), None, system=object()),
        lambda: api.config(tuned_defaults=True),
        lambda: kops.overrides(tuned_defaults=True),
    ], ids=["engine-autotune", "engine-power_cap_mw",
            "engine-persist_tuned_defaults", "engine-system",
            "config-tuned_defaults", "overrides-tuned_defaults"])
    def test_tuned_keywords_rejected(self, build):
        with pytest.raises(TypeError, match="unexpected keyword"):
            build()

    def test_engine_takes_only_its_serving_arguments(self):
        assert list(inspect.signature(ServeEngine).parameters) == [
            "cfg", "params", "max_len", "batch", "temperature", "seed"]

    def test_train_launcher_has_no_autotune_flag(self, capsys):
        from repro.launch.train import main
        with pytest.raises(SystemExit) as ei:
            main(["--autotune", "--steps", "0"])
        assert ei.value.code == 2
        assert "unrecognized arguments: --autotune" in capsys.readouterr().err


class TestTarget:
    def test_single_pe_is_one_core_cluster(self):
        t = api.Target.single_pe()
        assert t.n_cores == 1 and not t.is_heterogeneous
        assert t.core_points == (api.NOMINAL_POINT,)

    def test_homogeneous_preserves_shared_resources(self):
        cfg = api.ClusterConfig(tcdm_banks=64)
        t = api.Target.homogeneous(n_cores=4, cluster=cfg)
        assert t.cluster.tcdm_banks == 64 and t.n_cores == 4

    def test_heterogeneous_from_spec_string(self):
        t = api.Target.heterogeneous("2@1.45GHz@1.00V,6@0.50GHz@0.60V")
        assert t.is_heterogeneous and t.n_cores == 8
        assert t.strategy == "lpt"
        assert len(set(t.core_points)) == 2

    def test_rejects_unknown_strategy(self):
        with pytest.raises(ValueError, match="strategy"):
            api.Target(strategy="round_robin")

    def test_report_point_property(self):
        hom = api.evaluate("expf", api.Target.homogeneous(n_cores=2))
        assert hom.point == api.NOMINAL_POINT
        het = api.evaluate("expf",
                           api.Target.heterogeneous("1@1.45GHz@1.00V,"
                                                    "1@0.50GHz@0.60V"))
        with pytest.raises(ValueError, match="core_points"):
            het.point


class TestKernelRegistry:
    def test_every_historical_name_resolves(self):
        for name in KERNELS:
            assert api.kernel(name).isa_name == name
        assert api.kernel("montecarlo").isa_name == "pi_xoshiro128p"
        assert api.kernel("prng").tunable
        assert not api.kernel("prng").simulatable

    def test_unknown_kernel_names_known_set(self):
        with pytest.raises(KeyError, match="montecarlo"):
            api.kernel("nope")

    def test_tuner_only_kernel_rejected_by_evaluate(self):
        with pytest.raises(ValueError, match="tuner-only"):
            api.evaluate("softmax", api.Target.single_pe())

    def test_register_kernel_hook_and_overwrite_guard(self):
        spec = api.KernelSpec("user_exp", isa_name="expf",
                              aliases=("my_exp",))
        try:
            api.register_kernel(spec)
            assert api.kernel("my_exp") is spec
            # The registered kernel evaluates through its ISA binding.
            r = api.evaluate("user_exp", api.Target.single_pe())
            assert r.name == "expf"
            with pytest.raises(ValueError, match="overwrite=True"):
                api.register_kernel(api.KernelSpec("user_exp"))
            api.register_kernel(api.KernelSpec("user_exp", isa_name="logf",
                                               aliases=("my_exp",)),
                                overwrite=True)
            assert api.kernel("user_exp").isa_name == "logf"
        finally:
            from repro.api import registry as _k
            _k._REGISTRY.pop("user_exp", None)
            _k._ALIASES.pop("my_exp", None)

    def test_spec_binds_max_block(self):
        assert api.kernel("expf").max_block == TABLE_I["expf"].max_block

    def test_overwrite_reclaims_alias_names(self):
        """Regression: registering over an existing *alias* must purge the
        stale alias mapping, or kernel() would resolve past the new spec."""
        from repro.api import registry as _k
        snap_reg, snap_ali = dict(_k._REGISTRY), dict(_k._ALIASES)
        try:
            spec = api.KernelSpec("montecarlo", isa_name="pi_lcg")
            api.register_kernel(spec, overwrite=True)
            assert api.kernel("montecarlo") is spec
        finally:
            _k._REGISTRY.clear(); _k._REGISTRY.update(snap_reg)
            _k._ALIASES.clear(); _k._ALIASES.update(snap_ali)


class TestParseIslandsErrors:
    """Satellite: errors name the offending token and the grammar."""

    @pytest.mark.parametrize("spec,needle", [
        ("", "empty island spec"),
        ("2@1.45GHz@1.00V,,6@0.50GHz@0.60V", "island 2"),
        ("two@1.00GHz@0.80V", "'two' is not an integer"),
        ("2", "no '@<point-name>' part"),
        ("0@1.00GHz@0.80V", "core count must be >= 1"),
        ("2@9.99GHz@9.99V", "'9.99GHz@9.99V' is not in the ladder"),
    ])
    def test_errors_name_token_and_grammar(self, spec, needle):
        with pytest.raises(ValueError) as ei:
            api.parse_islands(spec, api.SNITCH_CLUSTER)
        assert needle in str(ei.value)
        if spec:
            assert "<count>@<point-name>" in str(ei.value)


class TestConfigContextManager:
    """Satellite: the mutable kernel globals became scoped ContextVars."""

    def test_scoped_and_restored(self):
        from repro.kernels import ops as kops
        assert kops.current_impl() == "auto"
        with api.config(impl="reference"):
            assert kops.current_impl() == "reference"
            with api.config(impl="pallas"):
                assert kops.current_impl() == "pallas"
            assert kops.current_impl() == "reference"
        assert kops.current_impl() == "auto"

    def test_restores_on_error(self):
        from repro.kernels import ops as kops
        with pytest.raises(RuntimeError):
            with api.config(impl="reference"):
                raise RuntimeError("boom")
        assert kops.current_impl() == "auto"

    def test_rejects_unknown_impl(self):
        with pytest.raises(ValueError, match="unknown impl"):
            with api.config(impl="cuda"):
                pass  # pragma: no cover

    def test_concurrent_threads_do_not_race(self):
        """The failure mode the satellite targets: an override in one
        thread must be invisible to a concurrently running benchmark."""
        from repro.kernels import ops as kops
        inside = threading.Event()
        release = threading.Event()
        seen = {}

        def override_thread():
            with api.config(impl="pallas"):
                inside.set()
                release.wait(5)

        def observer_thread():
            inside.wait(5)
            seen["impl"] = kops.current_impl()
            release.set()

        t1 = threading.Thread(target=override_thread)
        t2 = threading.Thread(target=observer_thread)
        t1.start(); t2.start()
        t1.join(5); t2.join(5)
        assert seen["impl"] == "auto"


class TestTuner:
    def test_methods_share_one_cache(self, tmp_path):
        from repro.tune import TuneCache
        cache = TuneCache(tmp_path / "cache.json")
        tuner = api.Tuner(cache=cache)
        tuner.block("prng")
        tuner.plan("prng")
        assert tuner.cache is cache
        assert len(cache) == 2          # both searches landed in one store
        assert tuner.block("prng").from_cache

    def test_plan_matches_legacy_tune(self):
        from repro.tune import tune
        legacy = tune("prng", cache=False)
        new = api.Tuner(cache=False).plan("prng")
        assert new.best == legacy.best
        assert new.best_cost == legacy.best_cost

    def test_operating_point_matches_legacy(self):
        from repro.tune import select_operating_point
        legacy = select_operating_point("expf", n_cores=8,
                                        power_cap_mw=350.0, cache=False)
        new = api.Tuner(api.Target.homogeneous(power_cap_mw=350.0),
                        cache=False).operating_point("expf", n_cores=8)
        assert new.best == legacy.best
        assert new.best_cost == legacy.best_cost

    def test_accepts_spec_objects_and_aliases(self):
        tuner = api.Tuner(cache=False)
        by_alias = tuner.block("montecarlo")
        by_spec = tuner.block(api.kernel("pi_xoshiro128p"))
        assert by_alias.workload == by_spec.workload == "montecarlo"

    def test_bound_objective_applies_to_every_method(self):
        """Regression: Tuner(objective=...) must bind operating_point too,
        not just plan/block."""
        tuner = api.Tuner(api.Target.homogeneous(power_cap_mw=350.0),
                          objective="edp", cache=False)
        assert tuner.plan("prng").objective == "edp"
        assert tuner.operating_point("prng").objective == "edp"
        # Default Tuner keeps the per-method historical defaults.
        plain = api.Tuner(cache=False)
        assert plain.plan("prng").objective == "cycles"
        assert plain.operating_point("prng").objective == "energy"


class TestPerIslandBlocks:
    """Satellite + acceptance: per-island block tuning never scores worse
    than the shared-block plan under the same power cap."""

    def test_uniform_island_blocks_canonicalize_to_shared(self):
        from repro.tune import Candidate, evaluate, get_workload
        w = get_workload("expf")
        shared = evaluate(w, Candidate(block=64, n_cores=8,
                                       islands=("1.45GHz@1.00V",
                                                "0.50GHz@0.60V"),
                                       strategy="lpt"))
        uniform = evaluate(w, Candidate(block=w.max_block, n_cores=8,
                                        islands=("1.45GHz@1.00V",
                                                 "0.50GHz@0.60V"),
                                        strategy="lpt",
                                        island_blocks=(64, 64)))
        assert uniform == shared

    def test_island_blocks_validation(self):
        from repro.tune import Candidate, evaluate, get_workload
        w = get_workload("expf")
        with pytest.raises(ValueError, match="one-for-one"):
            evaluate(w, Candidate(block=64, n_cores=8,
                                  islands=("1.00GHz@0.80V",),
                                  island_blocks=(64, 32)))
        with pytest.raises(ValueError, match="outside"):
            evaluate(w, Candidate(block=64, n_cores=8,
                                  islands=("1.00GHz@0.80V",
                                           "0.50GHz@0.60V"),
                                  island_blocks=(64, w.max_block + 1)))

    @pytest.mark.parametrize("cap", [None, 250.0])
    @pytest.mark.parametrize("name", ["expf", "softmax"])
    def test_never_worse_than_shared_block(self, name, cap):
        from repro.tune.cost import objective_value
        tuner = api.Tuner(api.Target.homogeneous(power_cap_mw=cap),
                          cache=False)
        shared = tuner.operating_point(name, heterogeneous=True,
                                       objective="edp")
        refined = tuner.operating_point(name, heterogeneous=True,
                                        objective="edp",
                                        per_island_blocks=True)
        assert objective_value(refined.best_cost, "edp") \
            <= objective_value(shared.best_cost, "edp")
        if cap is not None and shared.best_cost.feasible:
            assert refined.best_cost.power_mw <= cap

    def test_candidate_round_trips_island_blocks(self):
        import json

        from repro.tune import Candidate
        c = Candidate(block=64, n_cores=8,
                      islands=("1.45GHz@1.00V", "0.50GHz@0.60V"),
                      strategy="lpt", island_blocks=(128, 32))
        back = Candidate.from_dict(json.loads(json.dumps(c.to_dict())))
        assert back == c and isinstance(back.island_blocks, tuple)

    def test_from_dict_tolerates_old_payloads(self):
        from repro.tune import Candidate
        old = Candidate(block=64).to_dict()
        del old["island_blocks"]        # a pre-facade cache payload
        assert Candidate.from_dict(old) == Candidate(block=64)


class TestFacadeHelpers:
    def test_compare_strategies_keys(self):
        t = api.Target.heterogeneous("1@1.45GHz@1.00V,1@0.50GHz@0.60V")
        res = api.compare_strategies("expf", t, total_blocks=6)
        assert set(res) == set(STRATEGIES)
        assert all(isinstance(r, api.Report) for r in res.values())

    def test_headline_matches_cluster_export(self):
        from repro.cluster import headline as cluster_headline
        assert api.headline is cluster_headline

    def test_scaling_helpers_do_not_warn(self):
        """The still-supported analytics helpers migrated internally: no
        DeprecationWarning leaks from them."""
        from repro.cluster import strong_scaling, weak_scaling
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            weak_scaling("poly_lcg", cores=(1, 2))
            strong_scaling("poly_lcg", cores=(1, 2), total_blocks=4)
