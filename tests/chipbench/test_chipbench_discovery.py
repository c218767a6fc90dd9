"""A cell, a configuration, a traffic mix and a per-layer metric added as
new files plus new entries in BENCHMARK.json are found by name and run,
with no edit to an existing file."""

import json
import time
from types import SimpleNamespace

from chipbench import harness
from chipbench_cells import write

METRIC = '''
def read(v):
    return float(len(v.window.items))
'''


def test_new_files_are_found_and_run(root):
    cfg = json.loads((root / "chipbench/configs/tiny-olmo.json").read_text())
    write(root / "chipbench/configs/tiny-olmo-3l.json", dict(cfg, num_hidden_layers=3))
    traffic = json.loads((root / "chipbench/traffic/tiny-serve.json").read_text())
    write(root / "chipbench/traffic/tiny-greedy.json", dict(traffic, temperature=0.0))
    write(root / "chipbench/limits/tiny.new.json", {"logit_gap": {"limit": 0.3}})
    (root / "chipbench/metrics/requests_seen.py").write_text(METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-olmo-3l", "source": "test", "reduced": [],
                             "file": "chipbench/configs/tiny-olmo-3l.json", "why": "test"})
    bench["workloads"].append({"name": "tiny.new", "config": "tiny-olmo-3l",
                               "traffic": "tiny-greedy", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tiny.new")
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests", "better": "higher",
                               "source": "host_clock", "layer": "engine",
                               "moves": "serve_tokens_per_s", "workloads": ["tiny.new"]})
    write(root / "BENCHMARK.json", bench)

    cell = harness.load_cell("tiny.new", root)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["temperature"] == 0.0
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    window = SimpleNamespace(items=[1, 2, 3])
    assert harness.metric_reader("requests_seen", root)(SimpleNamespace(window=window)) == 3.0

    r = harness.run_cell(cell, 12345678901, 0.5, False, time.perf_counter(), platform="cpu")
    assert r["correct"], r
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
