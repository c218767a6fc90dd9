"""The TPU stack imports no module of the Snitch simulator.

The repo is two stacks.  The TPU stack (``configs``/``parallel``,
``kernels``, ``models``, ``train``, ``serve.engine``, ``launch``) is what
runs on the chip; the simulator (``api``, ``tune``, ``cluster``, ``core``,
``perf``, ``system``, ``resilience`` and ``serve.{sim,policies,traffic}``)
prices the paper's cluster analytically.  Imports may point from the
simulator into the TPU stack, never back: each case imports one TPU-stack
module in a fresh interpreter and lists the simulator modules it loaded.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

SIMULATOR_PACKAGES = ("api", "tune", "cluster", "core", "perf", "system",
                      "resilience")
SIMULATOR_SERVE = ("repro.serve.sim", "repro.serve.policies",
                   "repro.serve.traffic")

_PROBE = """
import importlib, json, sys
importlib.import_module(sys.argv[1])
print(json.dumps(sorted(m for m in sys.modules if m.startswith("repro."))))
"""


def _simulator_modules(loaded):
    return [m for m in loaded
            if m.split(".")[1] in SIMULATOR_PACKAGES or m in SIMULATOR_SERVE]


@pytest.mark.parametrize("module", [
    "repro.serve.engine", "repro.launch.serve", "repro.launch.train",
    "repro.train.train_step", "repro.models.model", "repro.kernels"])
def test_tpu_stack_loads_no_simulator_module(module):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(SRC)] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run([sys.executable, "-c", _PROBE, module], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    loaded = json.loads(out.stdout.splitlines()[-1])
    assert module in loaded
    assert _simulator_modules(loaded) == []
