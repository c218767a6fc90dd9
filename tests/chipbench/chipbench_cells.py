"""Tiny cells for the benchmark's tests: a copy of the benchmark's layout
under a temporary root, with small configurations, short traffic and
limits of their own, run on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "max_position_embeddings": 64,
}
SERVE = {"kind": "closed_batches", "batch": 2, "prompt_len": 8, "new_tokens": 4,
         "max_len": 16, "temperature": 0.8, "greedy_every": 2,
         "check_requests": 2, "check_sampled": 6, "trace_seconds": 1}
TRAIN = {"kind": "train_steps", "batch": 2, "seq_len": 16, "checked_steps": 3,
         "z_loss_weight": 1e-4, "trace_seconds": 1,
         "optimizer": {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                       "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 100,
                       "total_steps": 10000, "min_lr_ratio": 0.1}}
#: Limits of the tiny cells, set from their own CPU readings (tests below
#: print them): far above a sound run, far below a broken one.
#: sample_z, 7 seeds: sound 0.31-1.70, sampled requests served greedily or
#: with constant uniforms 11.0-11.9.
LIMITS = {"tiny.serve": {"logit_gap": {"limit": 0.3}, "sample_z": {"limit": 5.0}},
          "tiny.train": {"loss_gap": {"limit": 0.02}, "grad_gap": {"limit": 0.05},
                         "update_gap": {"limit": 0.3}}}


def write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: Path, sizes: dict = TINY, serve: dict = SERVE, train: dict = TRAIN,
              limits: dict = LIMITS) -> Path:
    """A benchmark root with two tiny cells and the real metric readers."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copytree(REPO / "chipbench" / "metrics", tmp / "chipbench" / "metrics")
    olmo = json.loads((REPO / "chipbench/configs/olmo-1b.json").read_text())
    phi = json.loads((REPO / "chipbench/configs/phi3-mini-3.8b-4l.json").read_text())
    write(tmp / "chipbench/configs/tiny-olmo.json", dict(olmo, **sizes))
    write(tmp / "chipbench/configs/tiny-phi3.json", dict(phi, **sizes))
    write(tmp / "chipbench/traffic/tiny-serve.json", serve)
    write(tmp / "chipbench/traffic/tiny-train.json", train)
    for name, lim in limits.items():
        write(tmp / f"chipbench/limits/{name}.json", lim)
    bench = dict(real)
    bench["configs"] = [
        {"name": "tiny-olmo", "source": "test", "file": "chipbench/configs/tiny-olmo.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-phi3", "source": "test", "file": "chipbench/configs/tiny-phi3.json",
         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.serve", "config": "tiny-olmo", "traffic": "tiny-serve",
         "chips": 1, "why": "test"},
        {"name": "tiny.train", "config": "tiny-phi3", "traffic": "tiny-train",
         "chips": 1, "why": "test"}]
    serve_e2e = {"serve_tokens_per_s", "request_p90_s"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            serves = m["name"] in serve_e2e or m.get("moves") in serve_e2e
            m["workloads"] = ["tiny.serve"] if serves else ["tiny.train"]
    write(tmp / "BENCHMARK.json", bench)
    return tmp


