"""``repro.obs`` — tracing, metrics and spans must observe, never perturb.

The contract this module pins, in order of importance:

1. **Parity** — a traced run produces bit-for-bit the same cycle/energy
   numbers as an untraced one, with the ``repro.perf`` memo bypassed
   (``REPRO_TIMING_MEMO=0`` semantics), cold, and warm (property-based,
   mirroring ``tests/test_timing_energy.py``'s memo-transparency suite).
2. **Exact reconciliation** — the per-lane cycle aggregates in a traced
   ``api.evaluate`` sum back to the returned ``Report``'s cycle totals
   exactly (``obs.export.reconcile``).
3. The metrics registry, spans, exports, CLI, the serve-engine
   instrumentation and the benchmark-harness satellites.
"""

import json

import pytest

from repro import api, obs
from repro.core.isa import Instr
from repro.core.kernels_isa import baseline_trace, copift_schedule
from repro.core.timing import (CopiftSchedule, copift_block_timing,
                               copift_problem_timing, evaluate_kernel,
                               simulate_single_issue, thread_cycles)
from repro.perf import memo
from tests._hypothesis_compat import given, settings, st

from tests.test_timing_energy import _random_body


def _sim_bundle(body, fp_body, iters, block, contention):
    """One tuple of every traced timing front door over a drawn body."""
    sched = CopiftSchedule("prop", int_body=list(body),
                           fp_bodies=[list(fp_body)])
    return (simulate_single_issue(body, iters),
            thread_cycles(body, iters, contention),
            copift_block_timing(sched, block, contention),
            copift_problem_timing(sched, 8 * block, block))


def _fp_body(body):
    return [Instr("fmadd.d", "facc", ("facc", "loop:ssr0", "const:c"))] + \
        [i for i in body if i.opcode.startswith("f")][:4]


# ---------------------------------------------------------------------------
# 1. Trace-vs-cold parity (property-based)
# ---------------------------------------------------------------------------

class TestTracedParity:
    """Tracing must never change a number — against the memo-bypassed
    ground truth AND against warm-memo runs (where the recorder consults
    the memo for provenance only and re-simulates for events)."""

    @settings(max_examples=20, deadline=None)
    @given(spec=st.lists(st.tuples(st.integers(0, 8), st.integers(0, 5),
                                   st.integers(0, 5)),
                         min_size=1, max_size=14),
           iters=st.integers(1, 24),
           block=st.sampled_from((1, 2, 7, 8, 16, 33)),
           contention=st.sampled_from((0.0, 0.25, 0.4375)))
    def test_property_traced_equals_untraced(self, spec, iters, block,
                                             contention):
        body = _random_body(spec)
        fp_body = _fp_body(body)
        args = (body, fp_body, iters, block, contention)

        with memo.memo_disabled():
            truth = _sim_bundle(*args)
            # traced with the memo bypassed (REPRO_TIMING_MEMO=0 path)
            with obs.session(trace=True, metrics=True):
                traced_nomemo = _sim_bundle(*args)
        assert traced_nomemo == truth

        # traced against a cold memo (stores populated through the
        # recorder), then against a warm one (provenance = hit).
        memo.clear_all()
        with obs.session(trace=True, metrics=True) as s1:
            traced_cold = _sim_bundle(*args)
        with obs.session(trace=True, metrics=True) as s2:
            traced_warm = _sim_bundle(*args)
        untraced_warm = _sim_bundle(*args)
        assert traced_cold == traced_warm == untraced_warm == truth
        assert s1.recorder.memo_provenance["cold"] > 0
        assert s2.recorder.memo_provenance["hit"] > 0
        assert s2.recorder.memo_provenance["cold"] == 0

    @pytest.mark.parametrize("name", ("expf", "pi_lcg"))
    def test_registry_kernels_traced_equals_cold(self, name):
        block = 64
        args = (name, baseline_trace(name), copift_schedule(name), block)
        with memo.memo_disabled():
            truth = evaluate_kernel(*args)
        memo.clear_all()
        with obs.session():
            traced_cold = evaluate_kernel(*args)
        with obs.session():
            traced_warm = evaluate_kernel(*args)
        assert traced_cold == traced_warm == truth

    def test_traced_run_does_not_poison_memo(self):
        """A traced run must leave the memo in the same state an untraced
        run would — populated with identical values (stores are never
        bypassed, never duplicated)."""
        sched = copift_schedule("expf")
        memo.clear_all()
        with obs.session():
            traced = copift_block_timing(sched, 64)
        after_traced = {s["name"]: s["entries"] for s in memo.stats()}
        memo.clear_all()
        untraced = copift_block_timing(sched, 64)
        after_untraced = {s["name"]: s["entries"] for s in memo.stats()}
        assert traced == untraced
        assert after_traced == after_untraced
        # and a post-session lookup serves the traced run's stores
        assert copift_block_timing(sched, 64) == untraced


# ---------------------------------------------------------------------------
# 2. Report parity + exact reconciliation through api.evaluate
# ---------------------------------------------------------------------------

TARGETS = {
    "single_pe": lambda: api.Target.single_pe(),
    "homogeneous8": lambda: api.Target.homogeneous(n_cores=8),
    "heterogeneous": lambda: api.Target.heterogeneous(
        "2@1.45GHz@1.00V,6@0.50GHz@0.60V"),
}


class TestEvaluateTraceReconcile:
    @pytest.mark.parametrize("target_name", sorted(TARGETS))
    def test_report_parity_and_exact_reconcile(self, target_name):
        target = TARGETS[target_name]()
        memo.clear_all()
        plain = api.evaluate("expf", target)
        memo.clear_all()
        with obs.session() as sess:
            traced_cold = api.evaluate("expf", target)
        with obs.session() as sess_warm:
            traced_warm = api.evaluate("expf", target)
        assert traced_cold == plain == traced_warm
        for s, rep in ((sess, traced_cold), (sess_warm, traced_warm)):
            res = s.reconcile(rep)
            assert res["ok"], [c for c in res["checks"] if not c["ok"]]
            assert all(c["ok"] for c in res["checks"])

    def test_reconcile_accepts_exported_dict(self):
        """Reconciliation works on the serialized chrome-trace JSON too —
        a saved trace is auditable without the live recorder."""
        with obs.session() as sess:
            report = api.evaluate("expf", api.Target.homogeneous(n_cores=4))
        roundtrip = json.loads(json.dumps(sess.trace_dict()))
        res = obs.reconcile(roundtrip, report)
        assert res["ok"]

    def test_reconcile_flags_tampered_summary(self):
        with obs.session() as sess:
            report = api.evaluate("expf", api.Target.homogeneous(n_cores=2))
        sess.recorder.summaries[-1]["cycles_copift"] += 1
        assert not sess.reconcile(report)["ok"]

    def test_chrome_trace_json_valid(self, tmp_path):
        with obs.session() as sess:
            api.evaluate("expf", api.Target.homogeneous(n_cores=2))
        path = tmp_path / "trace.perfetto.json"
        sess.save(str(path))
        doc = json.loads(path.read_text())
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases <= {"X", "M"}
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and "ts" in e and "name" in e
        # spans ride along in the same trace (host pid)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "api.evaluate" in names
        assert doc["otherData"]["summaries"]

    def test_sweep_traced_parity(self):
        targets = [api.Target.homogeneous(n_cores=n) for n in (1, 8)]
        memo.clear_all()
        plain = api.sweep("logf", targets)
        with obs.session() as sess:
            traced = api.sweep("logf", targets)
        assert traced == plain
        # one summary per evaluate, each reconciling on its own lanes
        kinds = [s["kind"] for s in sess.recorder.summaries]
        assert kinds == ["evaluate", "evaluate"]
        for rep in traced:
            assert sess.reconcile(rep)["ok"]


# ---------------------------------------------------------------------------
# 3. Metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        from repro.obs.metrics import Registry
        r = Registry()
        r.counter("c").inc()
        r.counter("c").inc(4)
        r.gauge("g").set(2.5)
        for v in (1.0, 3.0):
            r.histogram("h").observe(v)
        snap = r.snapshot()
        assert snap["c"]["value"] == 5
        assert snap["g"]["value"] == 2.5
        assert snap["h"]["count"] == 2 and snap["h"]["mean"] == 2.0
        assert snap["h"]["min"] == 1.0 and snap["h"]["max"] == 3.0
        r.reset()
        assert r.snapshot() == {}

    def test_type_mismatch_raises(self):
        from repro.obs.metrics import Registry
        r = Registry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_module_helpers_noop_when_disabled(self):
        from repro.obs import metrics
        metrics.REGISTRY.reset()
        assert not metrics.enabled()
        metrics.inc("nothing")
        metrics.set_gauge("nothing.g", 1.0)
        metrics.observe("nothing.h", 1.0)
        assert metrics.REGISTRY.snapshot() == {}

    def test_stall_breakdown_identity(self):
        """The instrumented stall split must satisfy the issue identity:
        cycles == instructions + raw + wb_port (per simulated stream)."""
        memo.clear_all()
        with obs.session(trace=False, metrics=True) as sess:
            copift_block_timing(copift_schedule("expf"), 64)
        m = sess.metrics()
        issued = m["timing.issue.instructions"]["value"]
        cycles = m["timing.issue.cycles"]["value"]
        raw = m["timing.stall.raw_cycles"]["value"]
        wb = m["timing.stall.wb_port_cycles"]["value"]
        assert cycles == issued + raw + wb
        assert m["timing.mem.accesses"]["value"] > 0

    def test_cluster_metrics_flow(self):
        memo.clear_all()
        with obs.session(trace=False, metrics=True) as sess:
            api.evaluate("expf", api.Target.homogeneous(n_cores=8))
        m = sess.metrics()
        assert m["cluster.contention.stalls_per_access"]["count"] > 0
        assert m["cluster.dma.transfers"]["value"] >= 1
        assert m["cluster.dma.bytes"]["value"] > 0
        # memo warmth gauges land at session close
        assert "perf.memo.timing.hit_rate" in m
        assert 0.0 <= m["perf.memo.timing.hit_rate"]["value"] <= 1.0

    def test_tune_and_oracle_metrics(self):
        from repro.tune.search import tune
        with obs.session(trace=False, metrics=True) as sess:
            tune("softmax", cache=False)
        m = sess.metrics()
        assert m["tune.oracle.batches"]["value"] >= 1
        assert m["tune.oracle.candidates"]["value"] > 0
        assert "span.tune.search.seconds" in m
        assert "span.tune.evaluate_batch.seconds" in m

    def test_metrics_isolated_between_sessions(self):
        with obs.session(trace=False, metrics=True) as s1:
            obs.metrics.inc("test.counter", 3)
        with obs.session(trace=False, metrics=True) as s2:
            pass
        assert s1.metrics().get("test.counter", {}).get("value") == 3
        assert "test.counter" not in s2.metrics()


# ---------------------------------------------------------------------------
# 4. Spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_provenance(self):
        memo.clear_all()
        with obs.session() as sess:
            with obs.span("outer", label="x"):
                api.evaluate("expf", api.Target.single_pe())
        spans = {s["name"]: s for s in sess.recorder.spans}
        # depth is 1-based: top-level spans sit at 1, nested below
        assert spans["outer"]["depth"] == 1
        assert spans["api.evaluate"]["depth"] == 2
        assert spans["api.evaluate"]["memo_provenance"] in (
            "cold", "mixed")  # first touch simulates
        with obs.session() as sess2:
            api.evaluate("expf", api.Target.single_pe())
        sp = [s for s in sess2.recorder.spans
              if s["name"] == "api.evaluate"][0]
        assert sp["memo_provenance"] == "hit"

    def test_span_noop_without_session(self):
        with obs.span("free") as handle:
            assert handle is None

    def test_span_is_a_profiler_host_event(self, tmp_path):
        """Inside a ``jax.profiler`` trace every span is a host event, with
        or without a session; a session still records it."""
        with obs.session() as sess:
            events = _profiled_host_events(tmp_path, lambda: _nested_spans())
        names = [n for _, _, n in events]
        assert names.index("outer") < names.index("inner")
        (os_, oe, _), (is_, ie, _) = (e for e in events if e[2] in ("outer", "inner"))
        assert os_ <= is_ and ie <= oe
        assert {s["name"] for s in sess.recorder.spans} == {"outer", "inner"}

    def test_bypassed_span_reaches_no_sink(self, tmp_path):
        from repro.obs import record as obs_record
        with obs_record.hooks_bypassed():
            events = _profiled_host_events(tmp_path, lambda: _nested_spans())
        assert not {"outer", "inner"} & {n for _, _, n in events}


def _nested_spans():
    with obs.span("outer", label="x"):
        with obs.span("inner"):
            pass


def _profiled_host_events(tmp_path, fn):
    """(start_ns, end_ns, name) of the host events of a ``jax.profiler``
    trace around ``fn()``, in order of start."""
    import glob

    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in jax.profiler.ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events)


# ---------------------------------------------------------------------------
# 5. Recorder mechanics
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_event_caps_and_dropped_counter(self):
        body = [Instr("add", "r0", ("r1",)), Instr("mul", "r2", ("r0",))]
        with obs.session(max_events_per_stream=4, max_events=16) as sess:
            for _ in range(40):
                simulate_single_issue(body, 8)
        rec = sess.recorder
        assert len(rec.events) <= 16
        assert rec.dropped_events > 0
        # aggregates ignore the cap — they keep exact totals
        tot = sum(v.get("busy", 0) for v in rec.lane_micro.values())
        assert tot > len(rec.events)

    def test_timeline_renders(self):
        with obs.session() as sess:
            api.evaluate("expf", api.Target.single_pe())
        text = sess.timeline(width=72)
        assert "rv32g" in text and "fpss" in text
        assert "api.evaluate" in text

    def test_hooks_bypassed_disables_everything(self):
        from repro.obs import record
        with obs.session() as sess:
            with record.hooks_bypassed():
                assert record.active_recorder() is None
                assert not obs.metrics.enabled()
                simulate_single_issue([Instr("add", "r0", ("r1",))], 4)
            assert record.active_recorder() is sess.recorder
        assert sess.recorder.events == []


# ---------------------------------------------------------------------------
# 6. CLI (python -m repro.obs.trace)
# ---------------------------------------------------------------------------

class TestTraceCli:
    def test_simulatable_kernel(self, tmp_path, capsys):
        from repro.obs.trace import main
        out = tmp_path / "t.json"
        assert main(["expf", "--cores", "2", "--out", str(out)]) == 0
        text = capsys.readouterr().out
        assert "reconcile: ok=True" in text
        doc = json.loads(out.read_text())
        assert doc["traceEvents"]

    def test_tuner_only_kernel(self, tmp_path, capsys):
        from repro.obs.trace import main
        out = tmp_path / "t.json"
        assert main(["softmax", "--out", str(out)]) == 0
        text = capsys.readouterr().out
        assert "tuner-only" in text
        assert json.loads(out.read_text())["traceEvents"]

    def test_unknown_kernel_errors(self):
        from repro.obs.trace import main
        with pytest.raises(SystemExit):
            main(["nosuchkernel"])

    def test_json_output_simulatable(self, tmp_path, capsys):
        """--json prints a machine-readable doc (and --out still writes
        the Perfetto trace alongside it)."""
        from repro.obs.trace import main
        out = tmp_path / "t.json"
        assert main(["expf", "--cores", "2", "--json",
                     "--out", str(out)]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["schema"] == 1 and doc["kernel"] == "expf"
        assert doc["simulatable"] and doc["reconcile"]["ok"]
        assert doc["result"]["cycles_copift"] > 0
        assert doc["result"]["speedup"] > 1
        assert doc["lane_micro"] and doc["n_summaries"] >= 1
        assert json.loads(out.read_text())["traceEvents"]

    def test_json_output_tuner_only(self, capsys):
        from repro.obs.trace import main
        assert main(["softmax", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert not doc["simulatable"] and doc["reconcile"] is None
        assert doc["result"]["cycles"] > 0


# ---------------------------------------------------------------------------
# 7. Serve-engine instrumentation + error-message satellite
# ---------------------------------------------------------------------------

class TestServeEngine:
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_weights_cast_at_build_gauge(self, dtype):
        """``serve.weights.cast_at_build`` holds the bytes of the fp32
        matrices the engine cast when it was built: all of them where the
        compute dtype is bf16, none where it equals ``param_dtype``."""
        import jax

        from repro.configs import load_config
        from repro.models.model import init_params
        from repro.serve.engine import ServeEngine

        cfg = load_config("jamba-v0.1-52b", "smoke").replace(dtype=dtype)
        params = init_params(cfg, jax.random.PRNGKey(0))
        with obs.session(trace=False, metrics=True) as sess:
            ServeEngine(cfg, params, max_len=16, batch=2)
        matrices = sum(p.nbytes for p in jax.tree.leaves(params) if p.ndim >= 2)
        assert matrices > 0 and cfg.param_dtype == "float32"
        want = matrices if dtype == "bfloat16" else 0
        assert sess.metrics()["serve.weights.cast_at_build"]["value"] == want

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_generate_spans_each_host_step(self, tmp_path, temperature):
        """One ``generate`` call marks its host steps in order, one span
        per step (never per slot), all inside ``serve.generate``."""
        import jax
        import numpy as np

        from repro.configs import load_config
        from repro.models.model import init_params
        from repro.serve.engine import ServeEngine

        cfg = load_config("olmo-1b", "smoke")
        eng = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                          max_len=16, batch=2, temperature=temperature)
        prompts = np.ones((2, 4), np.int32)
        eng.generate(prompts, 3)
        events = [e for e in _profiled_host_events(
            tmp_path, lambda: eng.generate(prompts, 3))
            if e[2].startswith("serve.")]
        assert [n for _, _, n in events] == [
            "serve.generate", "serve.cache_init", "serve.prefill",
            "serve.sample", "serve.decode_step", "serve.sample",
            "serve.decode_step", "serve.sample", "serve.collect"]
        lo, hi, _ = events[0]
        assert all(lo <= s and e <= hi for s, e, _ in events[1:])
        steps = [(s, e) for s, e, _ in events[1:]]
        assert all(e1 <= s2 for (_, e1), (s2, _) in zip(steps, steps[1:]))


# ---------------------------------------------------------------------------
# 8. Benchmark-harness satellites
# ---------------------------------------------------------------------------

class TestBenchSatellites:
    def test_sections_help_lists_names(self, capsys):
        from benchmarks.run import main
        main(["--sections", "help"])
        out = capsys.readouterr().out
        for name in ("table1", "fig2", "perf", "obs"):
            assert name in out

    def test_unknown_section_points_at_help(self, capsys):
        from benchmarks.run import main
        with pytest.raises(SystemExit):
            main(["--sections", "nosuch"])
        assert "--sections help" in capsys.readouterr().err

    def test_obs_bench_format_and_gate(self):
        from benchmarks.obs_bench import MAX_DISABLED_OVERHEAD, format_lines
        doc = dict(reference_seconds=1.0, disabled_seconds=1.01,
                   enabled_seconds=5.0, disabled_overhead=0.01,
                   enabled_overhead=4.0,
                   max_disabled_overhead=MAX_DISABLED_OVERHEAD,
                   overhead_ok=True, parity=True)
        lines = format_lines(doc)
        assert any("obs.gate" in ln and "True" in ln for ln in lines)
        assert any("obs.parity" in ln for ln in lines)


# ---------------------------------------------------------------------------
# 9. Export edge cases (S3)
# ---------------------------------------------------------------------------

class TestExportEdgeCases:
    def test_chrome_trace_pinned_key_set(self):
        """The export schema is a contract for downstream tooling: the
        top-level and otherData key sets are pinned exactly."""
        with obs.session(metrics=True) as sess:
            api.evaluate("expf", api.Target.homogeneous(n_cores=2))
        doc = obs.chrome_trace(sess.recorder)
        assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert set(doc["otherData"]) == {
            "memo_provenance", "dropped_events", "lane_micro",
            "block_records", "summaries"}
        with_metrics = obs.chrome_trace(sess.recorder,
                                        metrics_snapshot={"g": 1.0})
        assert set(with_metrics["otherData"]) == {
            "memo_provenance", "dropped_events", "lane_micro",
            "block_records", "summaries", "metrics"}
        # and the whole thing stays JSON-serializable
        json.dumps(doc)

    @pytest.mark.parametrize("width", [1, 2, 3, 7])
    def test_render_timeline_tiny_widths(self, width):
        """Degenerate widths must render (clamped), never raise."""
        with obs.session() as sess:
            api.evaluate("expf", api.Target.homogeneous(n_cores=2))
        text = obs.render_timeline(sess.recorder, width=width)
        for lane_bit in ("int", "fpss", "rv32g"):
            assert lane_bit in text
        bars = [ln for ln in text.splitlines() if "|" in ln]
        assert bars  # every lane row draws its (tiny) bar

    def test_render_timeline_empty_recorder(self):
        rec = obs.TraceRecorder()
        assert obs.render_timeline(rec) == "(no lanes recorded)"

    def test_reconcile_empty_trace(self):
        """No evaluate summaries: reconcile reports a structured failure,
        never raises."""
        rec = obs.TraceRecorder()
        res = obs.reconcile(rec)
        assert not res["ok"] and res["summaries"] == 0
        assert res["checks"][0]["name"] == "summary_present"
        # exported-dict flavor of the same emptiness
        res2 = obs.reconcile(obs.chrome_trace(rec))
        assert not res2["ok"] and res2["summaries"] == 0

    def test_reconcile_exact_despite_dropped_events(self):
        """Micro-event caps drop events, never aggregates: a trace that
        dropped events still reconciles exactly against its Report."""
        with obs.session(max_events_per_stream=8, max_events=64) as sess:
            report = api.evaluate("expf", api.Target.homogeneous(n_cores=8))
        assert sess.recorder.dropped_events > 0
        res = sess.reconcile(report)
        assert res["ok"], [c for c in res["checks"] if not c["ok"]]
        # and the timeline notes the drop instead of hiding it
        assert "dropped" in obs.render_timeline(sess.recorder)


# ---------------------------------------------------------------------------
# 10. Plan-transformed evaluate: traced parity + serial combine
# ---------------------------------------------------------------------------

class TestEvaluatePlanTraced:
    def test_default_plan_matches_plain_evaluate(self):
        """evaluate(plan=default candidate) is the identity transform —
        bit-for-bit the plain report, traced or not."""
        from repro.tune import default_space, get_workload
        w = get_workload("expf")
        default = default_space(w).default
        target = api.Target.homogeneous(n_cores=4)
        memo.clear_all()
        plain = api.evaluate("expf", target)
        with obs.session() as sess:
            planned = api.evaluate("expf", target, plan=default)
        assert planned == plain
        assert sess.reconcile(planned)["ok"]

    def test_serial_plan_reconciles_with_sum_combine(self):
        """pipelined=False (paper Fig. 1f) serializes the int/FP phases:
        the traced summary records combine='sum' and reconcile checks
        int+fp == block_cycles instead of max(int, fp)."""
        from dataclasses import replace
        from repro.tune import default_space, get_workload
        w = get_workload("logf")
        serial = replace(default_space(w).default, pipelined=False)
        with obs.session() as sess:
            report = api.evaluate("logf", api.Target.homogeneous(n_cores=2),
                                  plan=serial)
        s = sess.recorder.summaries[-1]
        assert all(c["combine"] == "sum" for c in s["cores"])
        res = sess.reconcile(report)
        assert res["ok"], [c for c in res["checks"] if not c["ok"]]
        assert any(c["name"].startswith("serial_phase_sum")
                   for c in res["checks"])
        # serializing can never beat the pipelined overlap
        with obs.session():
            piped = api.evaluate("logf", api.Target.homogeneous(n_cores=2))
        assert report.cycles_copift >= piped.cycles_copift

    def test_island_plans_rejected(self):
        """evaluate(plan=) prices plan knobs only; DVFS-island knobs
        belong to the cluster scheduler and are rejected loudly."""
        from dataclasses import replace
        from repro.tune import default_space, get_workload
        w = get_workload("expf")
        cand = replace(default_space(w).default, islands=(("1.00GHz", 4),))
        with pytest.raises(ValueError, match="island"):
            api.evaluate("expf", api.Target.homogeneous(n_cores=4),
                         plan=cand)
