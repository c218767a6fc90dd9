"""Sharding rule-table tests: divisibility of every param leaf of every arch
against the production mesh axes, EP/TP selection, batch/SP specs, and a
small real-device lower+compile of the sharded train step (subprocess with
8 host devices)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, load_config
from repro.launch import specs as SP

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


class FakeMesh:
    """Shape-only stand-in (never touches devices)."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.size = int(np.prod(list(shape.values())))
        self.empty = False


def _rules(cfg, multipod=False):
    from repro.parallel.sharding import ShardingRules
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16} if multipod
                    else {"data": 16, "model": 16})
    return ShardingRules(cfg, mesh)


class TestRuleTable:
    @pytest.mark.parametrize("arch", ARCHS)
    @pytest.mark.parametrize("multipod", [False, True])
    def test_every_leaf_divisible(self, arch, multipod):
        """A PartitionSpec axis on a non-divisible dim is a lowering error —
        catch it here, not in the 512-device compile."""
        cfg = load_config(arch, "full")
        rules = _rules(cfg, multipod)
        params = SP.params_specs(cfg)
        pspecs = rules.params_pspecs(params)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            for dim, axis in zip(leaf.shape, tuple(spec)):
                if axis is None:
                    continue
                axes = axis if isinstance(axis, tuple) else (axis,)
                size = int(np.prod([rules.mesh.shape[a] for a in axes]))
                assert dim % size == 0, (arch, leaf.shape, spec)

    def test_tp_applied_to_big_matrices(self):
        cfg = load_config("qwen3-32b", "full")
        rules = _rules(cfg)
        pspecs = rules.params_pspecs(SP.params_specs(cfg))
        qspec = pspecs["stack"]["periods"]["sub0"]["attn"]["q"]["w"]
        assert "model" in tuple(qspec)

    def test_ep_for_divisible_expert_counts(self):
        assert _rules(load_config("deepseek-moe-16b", "full")).ep    # 64 % 16
        assert _rules(load_config("jamba-v0.1-52b", "full")).ep      # 16 % 16
        assert not _rules(load_config("grok-1-314b", "full")).ep     # 8 % 16

    def test_grok_falls_back_to_tp_moe(self):
        cfg = load_config("grok-1-314b", "full")
        rules = _rules(cfg)
        pspecs = rules.params_pspecs(SP.params_specs(cfg))
        up = pspecs["stack"]["periods"]["sub0"]["moe"]["experts"]["up"]
        t = tuple(up)
        assert t[-3] is None and t[-1] == "model"    # E unsharded, d_ff TP

    def test_fsdp_by_size(self):
        assert not _rules(load_config("olmo-1b", "full")).fsdp is None
        assert _rules(load_config("grok-1-314b", "full")).fsdp
        assert _rules(load_config("qwen2-vl-72b", "full")).fsdp

    def test_batch_spec_modes(self):
        cfg = load_config("rwkv6-1.6b", "full")   # 1.6B < TP threshold:
        rules = _rules(cfg)                       # model axis folds into DP
        assert not rules.use_tp
        train = tuple(rules.batch_spec(SHAPES["train_4k"]))
        assert "data" in (train[0] if isinstance(train[0], tuple)
                          else (train[0],))
        assert train[1] is None
        # long_500k: batch=1 → sequence sharding (SP)
        long = tuple(rules.batch_spec(SHAPES["long_500k"]))
        assert long[0] is None and long[1] is not None

    def test_tp_threshold(self):
        assert not _rules(load_config("olmo-1b", "full")).use_tp
        assert not _rules(load_config("gemma-2b", "full")).use_tp
        assert _rules(load_config("qwen3-32b", "full")).use_tp
        assert _rules(load_config("grok-1-314b", "full")).use_tp

    def test_kv_cache_spec_decode(self):
        cfg = load_config("qwen3-32b", "full")
        rules = _rules(cfg)
        cache = SP.cache_specs(cfg, SHAPES["decode_32k"])
        pspecs = rules.cache_pspecs(cache, SHAPES["decode_32k"])
        kspec = tuple(jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P))[0])
        assert ("data",) in kspec or "data" in kspec   # batch sharded
        assert "model" in kspec                        # Dh sharded


@pytest.mark.slow
class TestRealLowering:
    def test_sharded_train_step_compiles_on_8_devices(self):
        """End-to-end: the dryrun cell runner on a small host mesh."""
        script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import load_config, SHAPES
from repro.configs.base import ShapeConfig
from repro.parallel.sharding import ShardingRules
from repro.launch.mesh import make_mesh
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.specs import step_and_specs

cfg = load_config("olmo-1b", "smoke").replace(remat="full")
shape = ShapeConfig("t", 256, 8, "train")
mesh = make_mesh((4, 2), ("data", "model"))
rules = ShardingRules(cfg, mesh)
fn, args, in_sh = step_and_specs(cfg, shape, rules, mesh)
with jax.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
cb = collective_bytes(compiled.as_text())
assert sum(cb["counts"].values()) > 0, "sharded step must communicate"
print("OK", cb["counts"])
"""
        r = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=ENV,
                           capture_output=True, text=True, timeout=420)
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout
