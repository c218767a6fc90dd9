"""BENCHMARK.json against the benchmark's contract, and the files each of
its entries names."""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (REPO / p).is_dir()


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_and_units(m):
    assert NAME.match(m["name"]), m["name"]
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert TEXT.match(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").is_file()


def test_names_are_unique():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("c", BENCH["configs"], ids=[c["name"] for c in BENCH["configs"]])
def test_configs(c):
    assert NAME.match(c["name"]) and TEXT.match(c["source"]) and TEXT.match(c["why"])
    assert all(NAME.match(k) for k in c["reduced"])
    f = json.loads((REPO / c["file"]).read_text())
    assert f["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]])
def test_cells(w):
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and TEXT.match(w["why"])
    assert w["chips"] in (1, 4)
    assert (REPO / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert (REPO / "chipbench" / "limits" / f"{w['name']}.json").is_file()
    e2e = [m["name"] for m in BENCH["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
    assert layer and all(m["moves"] in e2e for m in layer)


#: The numbers each traffic kind's driver compares, by the traffic key
#: that turns each on.
CHECKS = {"closed_batches": {"logit_gap": "check_requests", "sample_z": "check_sampled"},
          "train_steps": {"loss_gap": "checked_steps", "grad_gap": "checked_steps",
                          "update_gap": "checked_steps"}}


@pytest.mark.parametrize("w", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]])
def test_every_number_compared_has_its_limit(w):
    """Each number a cell's traffic turns on has a limit in the cell's
    limits file, set between the readings it was set from."""
    traffic = json.loads((REPO / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((REPO / "chipbench" / "limits" / f"{w['name']}.json").read_text())
    on = {n for n, key in CHECKS[traffic["kind"]].items() if traffic.get(key, 0) > 0}
    assert on and on <= set(limits), (on, set(limits))
    for n in on:
        lim = limits[n]
        assert lim["lower"] < lim["limit"] < lim["upper"], (n, lim)
        assert max(lim["program"]) == lim["lower"], n
