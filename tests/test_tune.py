"""Autotuner invariants: searches agree with the exhaustive argmin, the
tuned plan never loses to the static default, the restricted tuner
reproduces the Table-I "Max Block" rule, and the persistent cache
round-trips."""

import pytest

from repro.cluster.topology import NOMINAL_POINT, SNITCH_CLUSTER
from repro.core.analytics import TABLE_I
from repro.core.copift import choose_block
from repro.tune import (BUILTIN_KERNELS, Candidate, TuneCache, cache_key,
                        default_space, evaluate, exhaustive_search,
                        get_workload, local_search, objective_value,
                        select_operating_point, successive_halving, tune)
from tests._hypothesis_compat import given, settings, st


def _restricted(space, **pins):
    for name, values in pins.items():
        space = space.with_values(name, values)
    return space


def _pin_plan_knobs(workload):
    """Fusion off, natural movers, pipelining on — the paper's setting."""
    space = default_space(workload)
    return _restricted(space,
                       fuse_fp=(False,),
                       movers=(workload.schedule().n_ssrs,),
                       pipelined=(True,))


class TestChooseBlock:
    def test_zero_requested_rejected(self):
        with pytest.raises(ValueError):
            choose_block(5, 0)

    def test_negative_requested_rejected(self):
        with pytest.raises(ValueError):
            choose_block(5, -3)

    def test_unset_returns_cap(self):
        assert choose_block(13) == TABLE_I["expf"].max_block

    def test_requested_clamped_to_cap(self):
        cap = choose_block(13)
        assert choose_block(13, cap + 100) == cap
        assert choose_block(13, 10) == 10


class TestSpace:
    @pytest.mark.parametrize("name", BUILTIN_KERNELS)
    def test_default_is_member_and_size_matches(self, name):
        space = default_space(get_workload(name))
        assert space.default in space
        assert space.size == sum(1 for _ in space.candidates())

    def test_neighbors_are_single_knob_moves(self):
        space = default_space(get_workload("expf"))
        d = space.default
        for n in space.neighbors(d):
            diffs = [k for k, v in n.to_dict().items()
                     if v != getattr(d, k)]
            assert len(diffs) == 1
            assert n in space

    def test_with_values_unknown_knob_raises(self):
        space = default_space(get_workload("expf"))
        with pytest.raises(KeyError):
            space.with_values("no_such_knob", (1,))

    def test_block_over_cap_rejected(self):
        w = get_workload("expf")
        with pytest.raises(ValueError):
            evaluate(w, Candidate(block=w.max_block + 1))


class TestTunedNeverWorse:
    """Acceptance: for every built-in kernel the tuned plan's predicted
    cycles are <= the default make_plan plan's."""

    @pytest.mark.parametrize("name", BUILTIN_KERNELS)
    def test_tuned_beats_or_matches_default(self, name):
        res = tune(name, cache=False)
        assert res.best_cost.cycles <= res.default_cost.cycles
        assert res.predicted_speedup >= 1.0


class TestPinnedMaxBlock:
    """At 1 core, the nominal DVFS point, no fusion (and the other plan
    knobs at the paper's defaults), the tuner must reproduce the Table-I
    "Max Block" choice in the steady-state regime the printed rule assumes
    (whole blocks — problem a multiple of the cap)."""

    @pytest.mark.parametrize("name,row", [("expf", "expf"), ("logf", "logf"),
                                          ("montecarlo", "pi_xoshiro128p")])
    def test_reproduces_table_i(self, name, row):
        w = get_workload(name)
        res = tune(w, problem=64 * w.max_block, space=_pin_plan_knobs(w),
                   cache=False)
        assert res.best.block == TABLE_I[row].max_block
        assert res.best.n_cores == 1
        assert res.best.point == NOMINAL_POINT.name


class TestSearchesAgree:
    def test_tune_equals_exhaustive_argmin(self):
        w = get_workload("logf")
        space = default_space(w)
        best, _ = exhaustive_search(w, space, w.default_problem)
        assert tune(w, cache=False).best == best.candidate

    @pytest.mark.parametrize("strategy", [local_search, successive_halving])
    def test_strategy_bounded_by_argmin_and_default(self, strategy):
        w = get_workload("prng")
        space = default_space(w)
        opt, _ = exhaustive_search(w, space, w.default_problem)
        got, _ = strategy(w, space, w.default_problem)
        d = evaluate(w, space.default, w.default_problem)
        assert opt.cost.cycles <= got.cost.cycles <= d.cycles

    @settings(max_examples=15, deadline=None)
    @given(blocks=st.sets(st.sampled_from((16, 32, 64, 98, 157)),
                          min_size=1, max_size=3),
           fuse=st.booleans(), pipe=st.booleans(),
           objective=st.sampled_from(("cycles", "energy", "edp")))
    def test_property_tune_is_exhaustive_argmin(self, blocks, fuse, pipe,
                                                objective):
        w = get_workload("expf")
        space = _restricted(
            default_space(w),
            block=tuple(sorted(blocks)),
            fuse_fp=(False, True) if fuse else (False,),
            pipelined=(True, False) if pipe else (True,))
        best, evaluated = exhaustive_search(w, space, 4096,
                                            objective=objective)
        got = tune(w, problem=4096, objective=objective, space=space,
                   cache=False)
        assert len(evaluated) == space.size
        assert got.best == best.candidate
        assert objective_value(got.best_cost, objective) == \
            objective_value(best.cost, objective)


class TestCache:
    def test_round_trip_and_persistence(self, tmp_path):
        cache = TuneCache(tmp_path / "cache.json")
        r1 = tune("prng", cache=cache)
        assert not r1.from_cache
        r2 = tune("prng", cache=cache)
        assert r2.from_cache
        assert r2.best == r1.best and r2.best_cost == r1.best_cost
        # A fresh handle on the same file sees the persisted entry.
        reread = tune("prng", cache=TuneCache(tmp_path / "cache.json"))
        assert reread.from_cache and reread.best == r1.best

    def test_key_covers_config_and_space(self, tmp_path):
        w = get_workload("expf")
        space = default_space(w)
        k1 = cache_key("expf", 4096, SNITCH_CLUSTER, "cycles", None, space)
        assert k1 == cache_key("expf", 4096, SNITCH_CLUSTER, "cycles", None,
                               space)
        assert k1 != cache_key("expf", 8192, SNITCH_CLUSTER, "cycles", None,
                               space)
        assert k1 != cache_key("expf", 4096, SNITCH_CLUSTER, "energy", None,
                               space)
        assert k1 != cache_key("expf", 4096,
                               SNITCH_CLUSTER.with_cores(4), "cycles", None,
                               space)
        assert k1 != cache_key("expf", 4096, SNITCH_CLUSTER, "cycles", None,
                               space.with_values("pipelined", (True,)))

    def test_corrupt_cache_file_is_treated_as_empty(self, tmp_path):
        p = tmp_path / "cache.json"
        p.write_text("{not json")
        cache = TuneCache(p)
        assert len(cache) == 0
        r = tune("prng", cache=cache)
        assert not r.from_cache
        assert len(TuneCache(p)) == 1

    def test_truncated_cache_file_falls_back_to_cold_search(self, tmp_path):
        """A snapshot cut mid-write (e.g. a killed process on a filesystem
        without atomic rename) must read as empty, then heal on the next
        flush."""
        p = tmp_path / "cache.json"
        warm = TuneCache(p)
        tune("prng", cache=warm)
        whole = p.read_text()
        p.write_text(whole[:len(whole) // 2])
        cold = TuneCache(p)
        assert len(cold) == 0
        r = tune("prng", cache=cold)
        assert not r.from_cache
        # The failed read did not poison the file: it is valid JSON again.
        assert len(TuneCache(p)) == 1

    def test_wrong_schema_cache_treated_as_empty(self, tmp_path):
        p = tmp_path / "cache.json"
        p.write_text('{"schema": 999, "entries": {"k": {}}}')
        assert len(TuneCache(p)) == 0
        p.write_text('["a", "list"]')
        assert len(TuneCache(p)) == 0

    def test_unwritable_cache_path_degrades_to_memory_only(self, tmp_path):
        """$REPRO_TUNE_CACHE at an unwritable location must not fail the
        tune() call: one RuntimeWarning, then in-memory caching."""
        import warnings

        blocked = tmp_path / "file-not-dir"
        blocked.write_text("")          # a *file* where a dir is needed
        p = blocked / "sub" / "cache.json"
        cache = TuneCache(p)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            r1 = tune("prng", cache=cache)
            r2 = tune("prng", cache=cache)
        assert not r1.from_cache
        assert r2.from_cache            # in-memory entry still serves hits
        assert [w for w in caught
                if issubclass(w.category, RuntimeWarning)]
        assert not blocked.is_dir()     # nothing was forced onto disk

    def test_readonly_directory_degrades_gracefully(self, tmp_path):
        import os
        import stat
        import warnings

        ro = tmp_path / "ro"
        ro.mkdir()
        ro.chmod(stat.S_IRUSR | stat.S_IXUSR)
        if os.access(ro, os.W_OK):      # pragma: no cover (running as root)
            pytest.skip("cannot make directory read-only here")
        try:
            cache = TuneCache(ro / "cache.json")
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                r = tune("prng", cache=cache)
            assert not r.from_cache
            assert [w for w in caught
                    if issubclass(w.category, RuntimeWarning)]
        finally:
            ro.chmod(stat.S_IRWXU)


class TestCacheWarm:
    """``python -m repro.tune.cache --warm``: the warming pass prices
    through ``Tuner.plan`` itself, so a later ``Tuner.plan`` call is a
    pure cache hit — byte-identical keys by construction."""

    def test_warm_then_plan_hits(self, tmp_path):
        from repro.api import Tuner
        from repro.tune import cache as tune_cache

        p = tmp_path / "warm.json"
        hits = tune_cache.warm(["expf"], path=p)
        assert hits == {"expf": False}        # first pass priced it
        res = Tuner(cache=TuneCache(p)).plan("expf")
        assert res.from_cache
        # A second warming pass is itself a pure hit.
        assert tune_cache.warm(["expf"], path=p) == {"expf": True}

    def test_cli_warm_and_clear(self, tmp_path, capsys):
        from repro.tune import cache as tune_cache

        p = tmp_path / "warm.json"
        tune_cache.main(["--warm", "--kernel", "prng", "--path", str(p)])
        out = capsys.readouterr().out
        assert "tune.cache.warm,prng,priced" in out
        assert "1_entries" in out
        tune_cache.main(["--clear", "--path", str(p)])
        out = capsys.readouterr().out
        assert "tune.cache.cleared" in out and "0_entries" in out


class TestClusterScope:
    def test_power_cap_respected(self):
        res = tune("expf", cluster=True, power_cap_mw=350.0,
                   objective="energy", cache=False)
        assert res.best_cost.feasible
        assert res.best_cost.power_mw <= 350.0

    def test_select_operating_point_in_ladder(self):
        res = select_operating_point("expf", n_cores=8, power_cap_mw=350.0,
                                     cache=False)
        names = {p.name for p in SNITCH_CLUSTER.operating_points}
        assert res.best.point in names
        assert res.best.n_cores == 8
        assert res.best_cost.power_mw <= 350.0


class TestIntegration:
    def test_make_plan_tune_uses_tuner_block(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "cache.json"))

        from repro.core.copift import PhaseDef, make_plan
        from repro.core.isa import Domain

        phases = [
            PhaseDef(fn=lambda x: {"a": x * 2.0}, domain=Domain.FP,
                     writes=("a",), extern_reads=("x",)),
            PhaseDef(fn=lambda a: {"y": a + 1.0}, domain=Domain.INT,
                     reads=("a",), extern_writes=("y",)),
        ]
        plan = make_plan("expf", phases, n_elements=4096, tune=True)
        cap = choose_block(sum(plan.buffers.values()))
        assert 1 <= plan.block <= cap
        # Unknown workloads keep the static rule instead of failing.
        plan2 = make_plan("not_a_workload", phases, n_elements=4096,
                          tune=True)
        assert plan2.block == cap

    def test_select_block_holds_plan_knobs(self):
        from repro.tune import select_block
        res = select_block("expf", cache=False)
        assert res.best.fuse_fp is False
        assert res.best.pipelined is True
        assert res.best.movers == get_workload("expf").schedule().n_ssrs
        assert res.best.n_cores == 1

    def test_tune_bench_generate_contract(self):
        from benchmarks.tune_bench import format_lines, generate
        doc = generate(tiny=True, cluster=False)
        assert {r["kernel"] for r in doc["kernels"]} == set(BUILTIN_KERNELS)
        for r in doc["kernels"]:
            assert r["predicted_speedup"] >= 1.0
            assert r["tuned_cycles"] <= r["default_cycles"]
        assert any(line.startswith("tune.expf,")
                   for line in format_lines(doc))
