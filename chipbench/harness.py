"""The harness core: one run of one cell, from the command line to the
result line.

A run loads the cell from ``BENCHMARK.json`` and the files it names, checks
the device, lets the traffic kind's driver set up and warm up, measures
for ``--seconds`` (or, with ``--trace 1``, traces a window of at most the
traffic's ``trace_seconds``), reads the device's peak memory, frees the
program's state, and lets the driver compare what the window produced
with the plain reference.  The end-to-end metrics are computed here from
the window's host-clock records; the per-layer metrics by the readers in
``metrics/``, from the trace with the program's spans and model scopes
(``scopes.ScopedTrace``).
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
#: JAX's persistent compilation cache, at a fixed path inside the checkout
#: (the path is part of the cache's key).
CACHE_DIR = ROOT / ".chipbench_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind a cell needs, or too few."""


# ---------------------------------------------------------------------------
# what a run reads
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path = ROOT


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` of ``root/BENCHMARK.json``, with its
    configuration, traffic, limits and the metrics it reports."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; choices: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    limits_path = root / "chipbench" / "limits" / f"{name}.json"
    limits = _json(limits_path) if limits_path.exists() else {}

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name, w["chips"], config, traffic, limits,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)], root)


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_class(kind: str):
    return importlib.import_module(f"chipbench.drivers.{kind}").Driver


def reference_module(config: dict):
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


# ---------------------------------------------------------------------------
# what a window records
# ---------------------------------------------------------------------------

@dataclass
class Item:
    """One request or training step: host times of sending it and of the
    host sync that ended it, and the tokens it produced or consumed."""
    sent: float
    done: float
    tokens: int


@dataclass
class Window:
    start: float
    items: list = field(default_factory=list)
    failed: int = 0

    @property
    def end(self) -> float:
        return max(i.done for i in self.items)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def tokens(self) -> int:
        return sum(i.tokens for i in self.items)


@dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def check(cell: Cell, name: str, value: float) -> Check:
    """``value`` against the cell's limit for ``name`` (a missing limit
    fails)."""
    limit = cell.limits.get(name, {}).get("limit", float("-inf"))
    return Check(name, float(value), float(limit))


# ---------------------------------------------------------------------------
# end-to-end metrics, from the window's host-clock records
# ---------------------------------------------------------------------------

def _rate(w: Window) -> float:
    return w.tokens / w.seconds


def _p90(w: Window) -> float:
    return float(np.percentile([i.done - i.sent for i in w.items], 90))


END_TO_END = {
    "serve_tokens_per_s": _rate,
    "request_p90_s": _p90,
    "train_tokens_per_s": _rate,
}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def process_start() -> float:
    """``time.perf_counter()`` reading of the moment this process started
    (from /proc where there is one, else now)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def accelerator(chips: int, platform: str = "tpu"):
    """The first ``chips`` devices, which must be of ``platform``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoChip(f"JAX's device is {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} devices; JAX sees {len(devices)}")
    return devices[:chips]


def enable_compile_cache(path: Path = CACHE_DIR) -> None:
    import jax

    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def _peaks(kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


_COMPILES = []


def _compile_requests() -> int:
    """Programs compiled or loaded from the compilation cache so far in
    this process (counted from the first call on)."""
    if not _COMPILES:
        from jax import monitoring

        _COMPILES.append(0)

        def count(event, **kw):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                _COMPILES[0] += 1

        monitoring.register_event_listener(count)
    return _COMPILES[0]


@dataclass
class View:
    """What a per-layer metric's reader sees."""
    trace: object
    window: Window
    config: dict
    traffic: dict
    peak: dict
    chips: int


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             platform: str = "tpu") -> dict:
    """Run ``cell`` once; return the result line's object."""
    import jax

    devices = accelerator(cell.chips, platform)
    if platform == "tpu":
        enable_compile_cache()
    annotate = annotator(trace)
    driver = driver_class(cell.traffic["kind"])(cell, seed, devices, annotate)
    driver.setup()
    length = min(seconds, cell.traffic["trace_seconds"]) if trace else seconds
    log_dir = None
    if trace:
        import jax.profiler

        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    before = _compile_requests()
    t_window = time.perf_counter()
    window = driver.window(length)
    in_window = _compile_requests() - before
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_window - t0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    driver.release()
    gc.collect()
    checks = driver.check()

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    if trace:
        from chipbench import scopes

        t_read = time.perf_counter()
        reduced = scopes.ScopedTrace(scopes.load(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        view = View(reduced, window, cell.config, cell.traffic,
                    _peaks(dev.device_kind), len(devices))
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        breakdown = {"device_ops": reduced.top_ops(), "idle_gaps": reduced.idle_gaps()}
        print(f"trace read and reduced in {time.perf_counter() - t_read:.3f} s",
              file=sys.stderr)
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else END_TO_END[m["name"]](window)
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = {"correct": bool(checks) and all(c.ok for c in checks) and window.failed == 0,
              "attempted": len(window.items), "failed": window.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    print(f"programs compiled or loaded inside the window: {in_window}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    t0 = process_start()
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
