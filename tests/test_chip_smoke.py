"""chip_smoke.py rehearsed on the CPU: every phase at tiny widths through
``run`` (Pallas kernels in interpret mode), the four-device phase on
virtual CPU devices, and the refusal to run without a TPU or outside the
checkout."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

#: The phases of the full plan at smoke widths, on the CPU.
TINY = dict(platform="cpu", variant="smoke", softmax_impl="pallas", batch=2,
            prompt_len=16, max_len=32, gen=4, train_layers=2, train_batch=4,
            train_seq=32)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module
    spec.loader.exec_module(module)
    return module


def _run(args, cwd=REPO, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_tiny_plan_runs_every_phase(capsys):
    cs = _load()
    result = cs.run(cs.Plan(**TINY))
    assert result == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                             "count": 1}}
    passed = [line for line in capsys.readouterr().out.splitlines()
              if line.startswith("[check] ok: ")]
    for what in ("prefill logits vs reference softmax",
                 "greedy: decoding is deterministic",
                 "sampled tokens differ from greedy ones",
                 "Pallas lcg uniforms", "training losses are finite",
                 "step-0 loss"):
        assert any(what in line for line in passed), what


def test_four_device_phase_on_virtual_devices():
    """The --chips 4 phase on four CPU devices.  FSDP is forced on, since
    smoke widths are under its threshold and the spread check needs it."""
    script = (
        "import importlib.util, json, sys\n"
        "from repro.parallel import sharding\n"
        "sharding.FSDP_THRESHOLD = 0\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', {SCRIPT!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "sys.modules['chip_smoke'] = cs\n"
        "spec.loader.exec_module(cs)\n"
        f"print(json.dumps(cs.run(cs.Plan(**{TINY!r}), 4)))\n")
    r = _run(["-c", script], extra_env={
        "PYTHONPATH": os.path.join(REPO, "src"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-4000:]
    assert "train state spread over 4 devices" in r.stdout
    assert json.loads(r.stdout.splitlines()[-1])["device"]["count"] == 4


def test_refuses_without_tpu():
    r = _run([SCRIPT])
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
