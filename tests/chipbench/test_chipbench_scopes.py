"""Program spans and model scopes read from a trace (``chipbench.scopes``)
and the per-layer readers built on them give known answers: on hand-made
traces whose answers are worked out below, on real profiler traces taken
here on the CPU, and on a decode request recorded on a TPU v5e
(``fixtures/scoped/``)."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, scopes
from chipbench import trace as tr
from chipbench_cells import REPO

FIXTURES = Path(__file__).resolve().parent / "fixtures"
NEW = ("device_idle_share.sample", "decode_step_weight_cast_ms",
       "decode_step_layer_scan_ms", "attention_ms_per_prefill",
       "attention_ms_per_step.train")


def op(name, start, end, op_name="", device="TPU:0"):
    return ["op", device, f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", start, end - start,
            op_name]


def mod(name, start, end, device="TPU:0"):
    return ["module", device, f"{name}(123)", start, end - start]


def host(name, start, end):
    return ["host", "", name, start, end - start]


P, S = "jit(prefill)", "jit(serve_step)"
BODY = "layer_scan/while/body/closed_call"
SERVE = [
    host("window", 0, 1000), host("request", 0, 1000), host("serve.generate", 10, 990),
    host("serve.prefill", 20, 30), host("serve.sample", 300, 400),
    host("serve.decode_step", 400, 410), host("serve.sample", 600, 700),
    host("serve.collect", 900, 950),
    # prefill: 250 ns of leaf ops
    mod("jit_prefill", 30, 290),
    op("convert_element_type.1", 30, 50, f"{P}/weight_cast/convert_element_type"),
    op("while.1", 50, 250, f"{P}/layer_scan/while"),
    op("dynamic-slice_fusion.1", 50, 60, f"{P}/layer_scan/while/body/dynamic_slice"),
    op("fusion.1", 60, 120, f"{P}/{BODY}/attention/dot_general"),
    op("copy.1", 120, 130),                               # the while's: layer_scan
    op("softmax_2d.6", 130, 170, f"{P}/{BODY}/attention/softmax_2d"),
    op("fusion.2", 170, 240, f"{P}/{BODY}/ffn/dot_general;{P}/{BODY}/ffn/mul"),
    op("fusion.3", 250, 280, f"{P}/readout/dot_general"),
    op("copy.2", 280, 290),                               # under no scope
    # one uniform launch of sampling
    mod("jit_uniform_2d", 310, 320),
    op("uniform_2d.1", 311, 319, "jit(uniform_2d)/pallas_call"),
    # two decode steps
    mod("jit_serve_step", 410, 590),
    op("convert_element_type.2", 410, 440, f"{S}/weight_cast/convert_element_type"),
    op("while.2", 440, 560, f"{S}/layer_scan/while"),
    op("dynamic-update-slice_fusion.1", 440, 470,
       f"{S}/layer_scan/while/body/dynamic_update_slice"),
    op("fusion.4", 470, 520, f"{S}/{BODY}/attention/dot_general"),
    op("fusion.5", 520, 560, f"{S}/{BODY}/ffn/dot_general"),
    op("fusion.6", 560, 590, f"{S}/readout/dot_general"),
    mod("jit_serve_step", 710, 890),
    op("convert_element_type.2", 710, 730, f"{S}/weight_cast/convert_element_type"),
    op("while.2", 730, 870, f"{S}/layer_scan/while"),
    op("dynamic-update-slice_fusion.1", 730, 750,
       f"{S}/layer_scan/while/body/dynamic_update_slice"),
    op("fusion.4", 750, 800, f"{S}/{BODY}/attention/dot_general"),
    op("fusion.5", 800, 870, f"{S}/{BODY}/ffn/dot_general"),
    op("fusion.6", 870, 890, f"{S}/readout/dot_general"),
]

T = "jit(train_step)"
TRAIN = [
    host("window", 0, 500), host("train_step", 0, 500),
    mod("jit_train_step", 0, 400),
    op("convert.1", 0, 20, f"{T}/jvp(weight_cast)/convert_element_type"),
    op("while.1", 20, 200, f"{T}/jvp(layer_scan)/while"),
    op("fusion.1", 20, 80, f"{T}/jvp(layer_scan)/while/body/closed_call/attention/dot_general"),
    op("fusion.2", 80, 200, f"{T}/jvp(layer_scan)/while/body/closed_call/ffn/dot_general"),
    op("while.2", 200, 350, f"{T}/transpose(jvp(layer_scan))/while"),
    op("fusion.3", 200, 260, f"{T}/transpose(jvp(layer_scan))/while/body/checkpoint/"
                             "rematted_computation/closed_call/attention/dot_general"),
    op("fusion.4", 260, 300, f"{T}/transpose(jvp(layer_scan))/while/body/closed_call/"
                             "transpose(jvp(attention))/dot_general"),
    op("fusion.5", 300, 350, f"{T}/transpose(jvp(layer_scan))/while/body/closed_call/ffn/mul"),
    op("fusion.6", 350, 400, f"{T}/optimizer/mul"),
]


def view(trace, **kw):
    return SimpleNamespace(trace=trace, chips=1, window=SimpleNamespace(items=[None]), **kw)


def read(name, v):
    return harness.metric_reader(name, REPO)(v)


def test_scope_path():
    assert scopes.scope_path(f"{T}/transpose(jvp(layer_scan))/while/body/closed_call/"
                             "transpose(jvp(attention))/dot_general") == ("layer_scan", "attention")
    assert scopes.scope_path(f"{S}/{BODY}/attention/mul;{S}/{BODY}/ffn/add") == \
        ("layer_scan", "attention")
    assert scopes.scope_path("jit(iota)/iota") == ()
    assert scopes.scope_path("") == ()
    assert scopes.program_id("jit_serve_step(5227310467466529345)") == 5227310467466529345


def test_scope_seconds():
    t = scopes.ScopedTrace(SERVE)
    runs, secs = t.scope_seconds("jit_prefill")
    assert runs == 1
    assert secs == pytest.approx({"weight_cast": 20e-9, "layer_scan": 20e-9, "attention": 100e-9,
                                  "ffn": 70e-9, "readout": 30e-9, "": 10e-9})
    runs, secs = t.scope_seconds("jit_serve_step")
    assert runs == 2
    assert secs == pytest.approx({"weight_cast": 50e-9, "layer_scan": 50e-9,
                                  "attention": 100e-9, "ffn": 110e-9, "readout": 50e-9})
    # backward and recomputed ops count under their forward's scope
    runs, secs = scopes.ScopedTrace(TRAIN).scope_seconds("jit_train_step")
    assert runs == 1
    assert secs == pytest.approx({"weight_cast": 20e-9, "attention": 160e-9, "ffn": 170e-9,
                                  "optimizer": 50e-9})


def _train_step_at(t0):
    """TRAIN's step, its program and ops, moved to start at ``t0``."""
    return [e[:3] + [e[3] + t0] + e[4:] for e in TRAIN if e[0] != "host"]


def test_scope_seconds_counts_the_steps_that_start_in_the_window():
    """A step that began before the window is left out whole and one that
    the window's end cuts is counted whole, as ``module_runs`` counts them:
    the per-step reading is that of one whole step."""
    events = [host("window", 300, 1000), host("train_step", 300, 1000)]
    events += _train_step_at(0) + _train_step_at(400) + _train_step_at(800)
    t = scopes.ScopedTrace(events)
    runs, secs = t.scope_seconds("jit_train_step")
    assert runs == 2
    assert secs == pytest.approx({"weight_cast": 40e-9, "attention": 320e-9, "ffn": 340e-9,
                                  "optimizer": 100e-9})
    assert read("attention_ms_per_step.train", view(t)) == pytest.approx(160e-6)
    secs["attention"] = 0.0             # the reduction is kept, not the caller's copy
    assert t.scope_seconds("jit_train_step")[1]["attention"] == pytest.approx(320e-9)


def test_an_op_of_no_duration_makes_no_container():
    """The runtime's 0 ns custom-calls that start with a fusion leave the
    fusion a leaf: the reduction is that of the trace without them."""
    marked = SERVE + [op("custom-call.1", 60, 60), op("custom-call.2", 170, 170),
                      op("custom-call.3", 440, 440)]
    for module in ("jit_prefill", "jit_serve_step"):
        assert scopes.ScopedTrace(marked).scope_seconds(module) == \
            pytest.approx(scopes.ScopedTrace(SERVE).scope_seconds(module))


def test_program_spans_and_idle():
    t = scopes.ScopedTrace(SERVE)
    assert [n for _, _, n in t.program_spans()] == [
        "serve.generate", "serve.prefill", "serve.sample", "serve.decode_step",
        "serve.sample", "serve.collect"]
    assert t.program_spans("serve.sample") == [(300, 400, "serve.sample"),
                                               (600, 700, "serve.sample")]
    # [300,400] holds the uniform launch [311,319]: 92 ns idle; [600,700]
    # none: 100 ns
    assert t.idle_s("serve.sample") == pytest.approx(192e-9)
    assert t.idle_s("serve.prefill") == pytest.approx(10e-9)


def test_idle_gaps_name_program_spans():
    # gaps [590,710], [890,1000], [319,410], [0,30], [290,311]
    assert scopes.ScopedTrace(SERVE).idle_gaps() == [
        ["serve.sample", pytest.approx(120e-9)], ["serve.collect", pytest.approx(110e-9)],
        ["serve.sample", pytest.approx(91e-9)], ["serve.generate", pytest.approx(30e-9)],
        ["serve.sample", pytest.approx(21e-9)]]


def test_readers_on_hand_made_traces():
    v = view(scopes.ScopedTrace(SERVE))
    assert read("device_idle_share.sample", v) == pytest.approx(19.2)
    assert read("decode_step_weight_cast_ms", v) == pytest.approx(25e-6)
    assert read("decode_step_layer_scan_ms", v) == pytest.approx(25e-6)
    assert read("attention_ms_per_prefill", v) == pytest.approx(100e-6)
    assert read("attention_ms_per_step.train", v) is None
    v = view(scopes.ScopedTrace(TRAIN))
    assert read("attention_ms_per_step.train", v) == pytest.approx(160e-6)
    assert read("device_idle_share.sample", v) is None
    assert read("decode_step_weight_cast_ms", v) is None


def test_readers_find_nothing_without_spans_or_scopes():
    """Where the trace is read without them, or the program has none, the
    new readers return None and raise nothing."""
    no_scopes = [e[:5] for e in SERVE if not e[2].startswith("serve.")]
    for t in (tr.Trace(no_scopes), scopes.ScopedTrace(no_scopes)):
        for name in NEW:
            assert read(name, view(t)) is None


def test_five_field_trace_reduces_as_before():
    events = json.load(gzip.open(FIXTURES / "prefill_request_v5e.json.gz", "rt"))
    a, b = tr.Trace(events), scopes.ScopedTrace(events)
    assert b.busy_s() == a.busy_s() and b.window_s() == a.window_s()
    assert b.module_runs() == a.module_runs()
    assert b.kernel("softmax_2d", module="jit_prefill") == a.kernel("softmax_2d", module="jit_prefill")
    assert b.top_ops() == a.top_ops() and b.idle_gaps() == a.idle_gaps()
    assert b.program_spans() == []
    assert b.scope_seconds("jit_prefill")[1].keys() == {""}


# -- real traces on the CPU ----------------------------------------------------

def _cpu_trace(tmp_path, fn):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    return str(tmp_path)


def test_program_span_is_kept_as_host_event(tmp_path):
    from repro.obs import span

    def work():
        with span("serve.sample", slot=0) as sp:
            assert sp is None
            with span("not.a.program.span"):
                pass

    host = [e for e in scopes.load(_cpu_trace(tmp_path, work)) if e[0] == "host"]
    assert [e[2] for e in host] == ["window", "serve.sample"]
    (_, _, _, ws, wd), (_, _, _, ss, sd) = host
    assert ws <= ss and ss + sd <= ws + wd


def test_trace_maps_instructions_to_scopes(tmp_path):
    """The HLO in a trace's metadata plane gives each instruction of the
    engine's programs its model scopes; the op events of the CPU name the
    same programs and instructions."""
    import glob

    import jax

    from chipbench_cells import TINY
    from chipbench import system
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    c = dict(json.loads((REPO / "chipbench/configs/olmo-1b.json").read_text()), **TINY)
    cfg = system.program_config(c)
    eng = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), max_len=16, batch=2)
    prompts = np.ones((2, 8), np.int32)
    eng.generate(prompts, 2)
    path = glob.glob(f"{_cpu_trace(tmp_path, lambda: eng.generate(prompts, 2))}/**/*.xplane.pb",
                     recursive=True)[0]
    names = scopes.op_names(path)
    seen = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "program_id" in stats:
                    op_name = names[int(stats["program_id"])][stats["hlo_op"]]
                    seen.setdefault(stats["hlo_module"], set()).update(scopes.scope_path(op_name))
    # (on the CPU the casts and the embedding's gather fuse into the ops
    # that read them, and take those ops' names)
    for module in ("jit_prefill", "jit_serve_step"):
        assert {"layer_scan", "norm", "attention", "ffn", "readout"} <= seen[module]


# -- the decode request recorded on a TPU v5e ----------------------------------

def test_recorded_decode_request():
    """One sampled olmo1b request on a TPU v5 lite (8 prompts of 512
    tokens, 8 new at T = 0.8), as ``chipbench.scopes.load`` read it there:
    the readers and the share of each program's device time under no
    model scope give what was computed on the chip."""
    events = json.load(gzip.open(FIXTURES / "scoped" / "decode_request_v5e.json.gz", "rt"))
    t = scopes.ScopedTrace(events)
    v = view(t)
    assert [n for _, _, n in t.program_spans()][:4] == [
        "serve.generate", "serve.cache_init", "serve.prefill", "serve.sample"]
    assert [len(t.program_spans(n)) for n in ("serve.sample", "serve.decode_step")] == [8, 7]
    unscoped = {}
    for module in ("jit_prefill", "jit_serve_step"):
        _, secs = t.scope_seconds(module)
        unscoped[module] = secs[""] / sum(secs.values())
    assert unscoped == pytest.approx({"jit_prefill": 0.009826528012728793,
                                      "jit_serve_step": 0.05276025350827976})
    assert {name for name, _ in t.idle_gaps()} <= {"serve.cache_init", "serve.decode_step",
                                                   "serve.sample"}
    assert read("device_idle_share.sample", v) == pytest.approx(10.193757072326488)
    assert read("decode_step_weight_cast_ms", v) == pytest.approx(8.843599142857142)
    assert read("decode_step_layer_scan_ms", v) == pytest.approx(17.712916428571436)
    assert read("attention_ms_per_prefill", v) == pytest.approx(113.74669699999995)
    assert read("attention_ms_per_step.train", v) is None


# -- the harness reads the trace with scopes: every reader as before -----------

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
EXISTING = [m for m in BENCH["per_layer"] if m["name"] not in NEW]


def _cell_view(trace, metric):
    """What ``metric``'s reader sees in the first cell that lists it, over
    the recorded request."""
    cell = harness.load_cell(metric["workloads"][0], REPO)
    peak = json.loads((REPO / "chipbench" / "peaks.json").read_text())["TPU v5 lite"]
    return harness.View(trace, SimpleNamespace(items=[None]), cell.config, cell.traffic,
                        peak, 1)


@pytest.fixture(scope="module")
def recorded():
    return json.load(gzip.open(FIXTURES / "scoped" / "decode_request_v5e.json.gz", "rt"))


@pytest.mark.parametrize("metric", EXISTING, ids=[m["name"] for m in EXISTING])
def test_existing_readers_read_the_same_with_scopes(recorded, metric):
    """Each per-layer metric that the harness read with ``trace.Trace``
    reads the same from ``scopes.ScopedTrace`` over the same trace."""
    plain = [e[:5] for e in recorded if e[0] != "host" or e[2] in tr.ANNOTATIONS]
    before = read(metric["name"], _cell_view(tr.Trace(plain), metric))
    after = read(metric["name"], _cell_view(scopes.ScopedTrace(recorded), metric))
    assert after == before


@pytest.mark.parametrize("name", NEW)
def test_new_readers_are_listed_and_read_a_number(recorded, name):
    """The five span and scope readers are per-layer metrics of
    ``BENCHMARK.json``; each reads a number where its cell's program runs:
    the serving ones on the recorded decode request, the training one on
    the hand-made training step."""
    metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
    events = TRAIN if name.endswith(".train") else recorded
    value = read(name, _cell_view(scopes.ScopedTrace(events), metric))
    assert isinstance(value, float) and value > 0
