"""The trace reduction gives known answers: on a hand-made trace whose
answers are worked out below, and on a trace recorded on a TPU v5e
(``fixtures/``), where the answers are recomputed by brute force on a
nanosecond grid."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def op(device, name, start, end):
    return ["op", device, f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p)", start, end - start]


def mod(device, name, start, end):
    return ["module", device, f"{name}(123)", start, end - start]


def host(name, start, end):
    return ["host", "", name, start, end - start]


HAND = [
    mod("TPU:0", "jit_prefill", 100, 400),
    op("TPU:0", "while.3", 110, 390),
    op("TPU:0", "softmax_2d.6", 120, 170),
    op("TPU:0", "fusion.1", 180, 380),
    mod("TPU:0", "jit_serve_step", 500, 600),
    op("TPU:0", "softmax_2d.10", 510, 530),
    op("TPU:0", "all-gather.1", 540, 580),
    op("TPU:0", "fusion.2", 585, 595),
    mod("TPU:0", "jit_uniform_2d", 700, 710),
    op("TPU:0", "uniform_2d.1", 701, 709),
    mod("TPU:1", "jit_train_step", 150, 350),
    op("TPU:1", "while.5", 190, 310),
    ["async", "TPU:1", "%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %p)", 200, 100],
    op("TPU:1", "fusion.9", 250, 260),
    host("window", 100, 900),
    host("request", 90, 620),
    host("batch", 620, 690),
    host("request", 690, 800),
]


@pytest.fixture
def hand():
    return tr.Trace(HAND)


def test_names():
    assert tr.instruction("%softmax_2d.6 = f32[8] custom-call()") == "softmax_2d.6"
    assert tr.base("softmax_2d.6") == "softmax_2d"
    assert tr.base("jit_serve_step(1077)") == "jit_serve_step"


def test_busy_and_idle(hand):
    # TPU:0: [110,390] + [510,530] + [540,580] + [585,595] + [701,709]
    # = 358 ns; TPU:1: [190,310] = 120 ns (the async op is not counted);
    # window 800 ns.
    assert hand.window_s() == pytest.approx(800e-9)
    assert hand.busy_s() == pytest.approx((358 + 120) / 2 * 1e-9)
    assert hand.idle_share() == pytest.approx(1 - 239 / 800)


def test_programs_and_kernels(hand):
    assert hand.module_runs() == (4, pytest.approx(610e-9))
    assert hand.module_runs("jit_serve_step") == (1, pytest.approx(100e-9))
    assert hand.kernel("softmax_2d") == (2, pytest.approx(70e-9))
    assert hand.kernel("softmax_2d", module="jit_prefill") == (1, pytest.approx(50e-9))
    assert hand.kernel("uniform_2d") == (1, pytest.approx(8e-9))


def test_exposed_collectives(hand):
    # TPU:0: the synchronous all-gather [540,580] is exposed whole, 40 ns;
    # TPU:1: the asynchronous all-reduce [200,300] less fusion.9
    # [250,260] = 90 ns, the enclosing while.5 being no compute of its own.
    assert hand.exposed_collective_s() == pytest.approx(65e-9)


def test_breakdown(hand):
    top = hand.top_ops()
    assert top[:3] == [["jit_prefill/fusion.1", pytest.approx(100e-9)],
                       ["jit_prefill/softmax_2d.6", pytest.approx(25e-9)],
                       ["jit_serve_step/all-gather.1", pytest.approx(20e-9)]]
    assert "jit_prefill/while.3" not in dict(top)
    assert "jit_train_step/while.5" not in dict(top)
    # TPU:0's gaps: [709,900] after the last request, [390,510] inside the
    # first, [595,701] while the next batch was made, then 10, 10 and 5 ns.
    gaps = hand.idle_gaps()
    assert gaps[:3] == [["none", pytest.approx(191e-9)], ["request", pytest.approx(120e-9)],
                        ["batch", pytest.approx(106e-9)]]
    assert len(gaps) == 6


def test_needs_one_window():
    with pytest.raises(ValueError):
        tr.Trace([e for e in HAND if e[2] != "window"])


def _grid(intervals, lo, hi):
    g = np.zeros(int(hi - lo), bool)
    for s, e in intervals:
        g[max(0, int(s - lo)):max(0, int(min(e, hi) - lo))] = True
    return g


@pytest.mark.parametrize("name", sorted(p.name for p in FIXTURES.glob("*.json.gz")))
def test_recorded_trace(name):
    events = json.load(gzip.open(FIXTURES / name, "rt"))
    t = tr.Trace(events)
    lo, hi = t.lo, t.hi
    ops = [(s, s + d) for k, dev, n, s, d in events if k == "op" and dev == t.devices[0]]
    busy = _grid(ops, lo, hi).sum()
    assert t.busy_s() * len(t.devices) == pytest.approx(busy * 1e-9 * len(t.devices), rel=1e-6, abs=2e-9 * len(ops))
    soft = [d for k, dev, n, s, d in events
            if k == "op" and lo <= s < hi and tr.base(tr.instruction(n)) == "softmax_2d"]
    assert t.kernel("softmax_2d") == (len(soft), pytest.approx(sum(soft) * 1e-9))
    mods = [d for k, dev, n, s, d in events if k == "module" and lo <= s < hi]
    assert t.module_runs() == (len(mods), pytest.approx(sum(mods) * 1e-9))
    gaps = t.idle_gaps()
    assert sum(g for _, g in gaps) <= t.window_s() - t.busy_s() + 1e-9


def test_recorded_request_metrics():
    """The readers on the recorded request (one olmo1b.prefill.greedy
    request on a TPU v5 lite) give what that run printed."""
    import json as _json
    from types import SimpleNamespace

    from chipbench import harness

    repo = FIXTURES.parents[2]
    events = json.load(gzip.open(FIXTURES / "prefill_request_v5e.json.gz", "rt"))
    view = SimpleNamespace(
        trace=tr.Trace(events), chips=1,
        window=SimpleNamespace(items=[None]),
        config=_json.loads((repo / "chipbench/configs/olmo-1b.json").read_text()),
        traffic=_json.loads((repo / "chipbench/traffic/prefill.greedy.json").read_text()),
        peak=_json.loads((repo / "chipbench/peaks.json").read_text())["TPU v5 lite"])
    read = lambda name: harness.metric_reader(name, repo)(view)
    assert read("engine_launches_per_step") == 6.5
    assert read("decode_step_device_ms") == pytest.approx(19.780203333333336)
    assert read("softmax_roofline.prefill") == pytest.approx(9.512717033239193)
    assert read("device_idle_share.serve") == pytest.approx(2.4033462049128596)
    assert read("uniform_roofline") is None
