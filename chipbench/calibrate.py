#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control 4] [--faults 3] --out <file.jsonl>

In one process on the chip, for each seed: the program's reading of each
number the cell compares, at the cell's own sizes, through the same
driver, entry points and comparison as a run (serving: only the greedy
and sampled requests a run would check, each independent of the others
in a closed loop).  For the first ``--control`` seeds also the control's:
the reference put in the program's place at fp8, the precision below the
configuration's bf16 (for ``sample_z``, the z-score that tokens drawn
from the fp8 distribution read in expectation).  For the first
``--faults`` seeds also the faults: in training, half of each batch left
out, planted in the reference put in the program's place; in serving, the
sampled requests served greedily, and served with every uniform reading
0.5, planted in the program.  One JSON line per seed.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402
from chipbench.drivers import closed_batches, train_steps  # noqa: E402


def serve(driver, seed, control, faults=False):
    from chipbench import system

    t, n = driver.t, driver.t["new_tokens"]
    driver.setup()
    first = range(10 * t["greedy_every"] * (t["check_requests"] + t.get("check_sampled", 0)))
    ids = [i for i in first if closed_batches.greedy(t, i)][:t["check_requests"]]
    sids = [i for i in first if not closed_batches.greedy(t, i)][:t.get("check_sampled", 0)]

    def prompt(i):
        return closed_batches.prompts(t, driver.c["vocab_size"], seed, i)

    for i in ids:
        driver.served[i] = driver.request(prompt(i), True, n)
    for i in sids:
        driver.sampled[i] = driver.request(prompt(i), False, n)
    broken = {}
    if faults and sids:
        broken["greedy_sampled"] = {i: driver.request(prompt(i), True, n) for i in sids}
        with system.constant_uniforms():
            broken["constant_uniform"] = {i: driver.request(prompt(i), False, n)
                                          for i in sids}
    driver.release()

    def numbers(ops=None):
        out = {"logit_gap": max(driver.gaps(ids, ops))} if ids else {}
        if sids:
            out["sample_z"] = driver.sample_z(sids, ops=ops)
        return out

    out = {"program": numbers()}
    if control:
        out["control"] = numbers(driver.ref.FP8)
    for name, tokens in broken.items():
        out[name] = {"sample_z": driver.sample_z(sids, tokens)}
    return out


def train(driver, seed, control, fault):
    driver.setup()
    driver.release()
    ref = driver.reference()
    out = {"program": train_steps.gaps(driver.readings(), ref)}
    if control:
        out["control"] = train_steps.gaps(driver.reference(driver.ref.FP8), ref)
    if fault:
        half = driver.reference(rows_kept=driver.t["batch"] // 2)
        out["half_batch"] = train_steps.gaps(half, ref)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=4)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    devices = harness.accelerator(cell.chips)
    harness.enable_compile_cache()
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = harness.driver_class(cell.traffic["kind"])(
            cell, seed, devices, harness.annotator(False))
        if cell.traffic["kind"] == "closed_batches":
            out = serve(driver, seed, k < args.control, k < args.faults)
        else:
            out = train(driver, seed, k < args.control, k < args.faults)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(out) + "\n")
        del driver
        gc.collect()


if __name__ == "__main__":
    main()
