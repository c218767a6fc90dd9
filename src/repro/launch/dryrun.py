import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks the device count on first
#   init).  Only the dry-run sees 512 placeholder devices; tests/benches
#   keep the default single device.

"""Multi-pod dry-run: for every (architecture × input shape × mesh) cell,
``jax.jit(step).lower(**input_specs).compile()`` must succeed on the 16×16
single-pod mesh AND the 2×16×16 two-pod mesh.  Per cell we record:

* ``compiled.memory_analysis()``  — per-device bytes (proves it fits),
* ``compiled.cost_analysis()``    — per-device FLOPs / bytes-accessed,
* collective bytes parsed from the compiled HLO (all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute operand+result sizes),
* lowering + compile wall time,

into ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` — the roofline
analysis (benchmarks/roofline.py, EXPERIMENTS.md §Roofline) reads these.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import SHAPES, applicable_shapes, load_config
from repro.configs.registry import ARCHS
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.parallel.sharding import ShardingRules

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

from repro.launch.hlo_analysis import collective_bytes


def run_cell(arch: str, shape_name: str, mesh_kind: str) -> dict:
    cfg = load_config(arch, "full")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rules = ShardingRules(cfg, mesh, shape)
    record = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                  devices=mesh.size, fsdp=rules.fsdp, ep=rules.ep,
                  n_params=cfg.n_params(),
                  n_active_params=cfg.n_active_params())
    t0 = time.time()
    fn, args, in_sh = SP.step_and_specs(cfg, shape, rules, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)

    ma = compiled.memory_analysis()
    record["memory"] = dict(
        argument_bytes=int(ma.argument_size_in_bytes),
        output_bytes=int(ma.output_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes),
        alias_bytes=int(ma.alias_size_in_bytes),
        code_bytes=int(ma.generated_code_size_in_bytes),
        total_bytes=int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
    )
    ca = compiled.cost_analysis()
    record["cost"] = {"flops": float(ca.get("flops", 0.0)),
                      "transcendentals": float(ca.get("transcendentals", 0.0)),
                      "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    record["collectives"] = collective_bytes(compiled.as_text())
    return record


def cells(archs=None, shapes=None):
    for arch in (archs or ARCHS):
        cfg = load_config(arch, "full")
        for sh in applicable_shapes(cfg):
            if shapes and sh not in shapes:
                continue
            yield arch, sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    todo = list(cells(args.arch, args.shape))
    failures = []
    for arch, sh in todo:
        for mk in meshes:
            tag = f"{arch}__{sh}__{mk}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, sh, mk)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                mem_gb = rec["memory"]["total_bytes"] / 2**30
                print(f"[ok] {tag}: mem/device={mem_gb:.2f}GiB "
                      f"flops/device={rec['cost']['flops']:.3e} "
                      f"coll={rec['collectives']['total_bytes']:.3e}B "
                      f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                      flush=True)
            except Exception as e:
                failures.append(tag)
                print(f"[FAIL] {tag}: {e}")
                traceback.print_exc()
    print(f"done: {len(todo) * len(meshes) - len(failures)} ok, "
          f"{len(failures)} failed {failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
