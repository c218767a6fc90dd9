"""``system.program_config`` builds the program's model configuration from a
configuration file: the benchmark's two files to the configs they have
always built, any registry architecture from a file whose ``program``
object states what sets it apart, and a config field the program gains
later with no edit to the benchmark; a file that departs from the
registry's architecture raises."""

import copy
import dataclasses
import json
from pathlib import Path

import jax
import pytest

from chipbench import system
from chipbench_cells import REPO
from repro.configs import ARCHS, ModelConfig, MoEConfig, load_config
from repro.models.model import init_params

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "configs"
MOE = json.loads((FIXTURES / "deepseek-moe-16b-smoke.json").read_text())

#: What the two files built before ``program_config`` became generic,
#: field for field.
PINNED = {
    "olmo-1b": ModelConfig(
        name="olmo-1b", family="dense", n_layers=16, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=8192, vocab_size=50304, d_head=128, norm="nonparam_ln",
        act="swiglu", rope="rope", rope_theta=10000.0, qk_norm=False, causal=True,
        tie_embeddings=True, embed_scale=False, moe=None, ssm=None,
        layer_types="a" * 16, mrope_sections=(16, 24, 24), frontend="none",
        max_seq_len=2048, sliding_window=0, dtype="bfloat16", param_dtype="float32",
        opt_state_dtype="float32", remat="full", use_copift_softmax=True,
        softmax_impl="auto", scan_layers=True, vocab_parallel_ce=False),
    "phi3-mini-3.8b-4l": ModelConfig(
        name="phi3-mini-3.8b", family="dense", n_layers=4, d_model=3072, n_heads=32,
        n_kv_heads=32, d_ff=8192, vocab_size=32064, d_head=96, norm="rmsnorm",
        act="swiglu", rope="rope", rope_theta=10000.0, qk_norm=False, causal=True,
        tie_embeddings=False, embed_scale=False, moe=None, ssm=None,
        layer_types="aaaa", mrope_sections=(16, 24, 24), frontend="none",
        max_seq_len=4096, sliding_window=0, dtype="bfloat16", param_dtype="float32",
        opt_state_dtype="float32", remat="full", use_copift_softmax=True,
        softmax_impl="auto", scan_layers=True, vocab_parallel_ce=False),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_benchmark_files_build_their_pinned_config(name):
    c = json.loads((REPO / "chipbench" / "configs" / f"{name}.json").read_text())
    cfg = system.program_config(c)
    assert type(cfg) is ModelConfig
    for f in dataclasses.fields(ModelConfig):
        assert getattr(cfg, f.name) == getattr(PINNED[name], f.name), f.name
    assert cfg == PINNED[name]


def test_moe_file_builds_its_experts_and_layer_pattern():
    cfg = system.program_config(MOE)
    assert cfg.moe == MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                                layer_pattern="all_but_first")
    assert (cfg.family, cfg.n_layers, cfg.layer_types) == ("moe", 3, "aaa")
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.dtype) == (64, 128, 256, "float32")
    # the program builds it: a dense first layer, then 64 experts of 1408
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    dims = {leaf.shape for leaf in jax.tree.leaves(shapes)}
    assert any(s[-3:] == (64, 64, 1408) for s in dims), dims
    assert len(shapes["stack"]["prefix"]) == 1


def _moe(**program):
    c = copy.deepcopy(MOE)
    for k, v in program.items():
        if v is None:
            c["program"].pop(k)
        else:
            c["program"][k] = v
    return c


def _with_moe(**kw):
    return _moe(moe=dict(MOE["program"]["moe"], **kw))


@pytest.mark.parametrize("c, match", [
    (_with_moe(top_k=4), "departs"),
    (_with_moe(layer_pattern="all"), "departs"),
    (_moe(moe=None), "departs"),
    (_moe(sliding_window=4096), "departs"),
    (_moe(n_routers=2), "not an architecture field"),
    (_moe(softmax_impl="reference"), "not an architecture field"),
    (_moe(opt_state_dtype="bfloat16"), "not an architecture field"),
    (_moe(name="deepseek-moe-16b-2l"), "not an architecture field"),
    (_with_moe(router="sigmoid"), "'moe'"),
    (_moe(act="geglu"), "file's own keys"),
    (_moe(d_model=128), "file's own keys"),
    (_moe(rope_theta=1e6), "file's own keys"),
], ids=["wrong_top_k", "wrong_layer_pattern", "no_moe", "unstated_window",
        "unknown_field", "reference_softmax", "bf16_optimizer_state", "name",
        "unknown_moe_field", "act_twice", "size_twice", "theta_twice"])
def test_departing_file_raises(c, match):
    with pytest.raises(ValueError, match=match):
        system.program_config(c)


def _json(value):
    if dataclasses.is_dataclass(value):
        return dataclasses.asdict(value)
    return list(value) if isinstance(value, tuple) else value


def registry_file(arch: str, depth: int) -> dict:
    """A configuration file for the registry's ``arch`` at its own widths
    and ``depth`` layers, whose ``program`` object states every
    architecture field that differs from a dense model's, as JSON."""
    cfg = load_config(arch).with_depth(depth)
    sizes = {k: getattr(cfg, f) for f, k in system._SIZES.items()}
    dense = ModelConfig(name=cfg.name, family=cfg.family,
                        **{f: getattr(cfg, f) for f in system._SIZES})
    program = {f: _json(getattr(cfg, f)) for f in system.architecture_fields(ModelConfig)
               if getattr(cfg, f) != getattr(dense, f)}
    return json.loads(json.dumps(dict(arch=arch, program=program, **sizes)))


@pytest.mark.parametrize("arch", ARCHS)
def test_every_registry_architecture_builds_from_a_file(arch):
    depth = min(8, load_config(arch).n_layers)
    c = registry_file(arch, depth)
    assert system.program_config(c) == load_config(arch).with_depth(depth)
    if c["program"]:
        del c["program"][sorted(c["program"])[0]]
        with pytest.raises(ValueError, match="departs"):
            system.program_config(c)


@dataclasses.dataclass(frozen=True)
class WithRouter(ModelConfig):
    """The registry's config class with a field the program might gain."""
    router: str = "softmax"


def test_a_new_config_field_needs_no_benchmark_edit(monkeypatch):
    real = load_config("deepseek-moe-16b")
    fields = {f.name: getattr(real, f.name) for f in dataclasses.fields(ModelConfig)}
    monkeypatch.setattr(system, "load_config",
                        lambda name: WithRouter(**fields, router="sigmoid"))
    assert "router" in system.architecture_fields(WithRouter)
    with pytest.raises(ValueError, match="router"):
        system.program_config(MOE)
    cfg = system.program_config(_moe(router="sigmoid"))
    assert (type(cfg), cfg.router, cfg.moe.top_k) == (WithRouter, "sigmoid", 6)
