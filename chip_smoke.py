#!/usr/bin/env python3
"""Bring-up check: OLMo-1B serves and trains end to end on a TPU.

    python chip_smoke.py              # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4    # four chips: sharded train step vs one device

Serve phase: olmo-1b at its published widths with all 16 layers and seeded
random weights, built into a ``ServeEngine`` as ``repro.launch.serve`` does.
It generates 32 tokens for 8 seeded prompts of 512 tokens (``max_len``
1024), once greedy and once at temperature 0.8, so the Pallas PRNG samples.
It checks that the compiled prefill holds a ``tpu_custom_call`` (the COPIFT
softmax ran as a kernel), that its logits agree with the reference-softmax
model, and that the Pallas uniforms equal their jnp oracle bit for bit.

Train phase: 3 steps of ``repro.launch.train.main`` at OLMo-1B widths with
depth cut to 8 layers (so weights, gradients and Adam state fit 16 GB), on
batches of 8 x 512 tokens.  Losses must be finite, step 0 near ln(vocab),
and step 0 equal to a reference-softmax run's.

``--chips 4`` runs only the sharded train step on a (data=2, model=2) mesh
built by ``ShardingRules``, and the same steps on one device, and compares
the losses.

One process does everything and starts no child.  A failed check raises,
so the exit code is non-zero and no result line is printed; the same holds
when JAX's first device is not a TPU.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import load_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.data.pipeline import PipelineConfig, TokenPipeline  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.specs import step_and_specs  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.parallel.sharding import ShardingRules  # noqa: E402
from repro.serve.engine import ServeEngine, make_cache, make_prefill  # noqa: E402
from repro.train.optimizer import AdamWConfig  # noqa: E402
from repro.train.train_step import init_train_state  # noqa: E402

#: Prefill logits, Pallas softmax vs reference softmax, max |difference|.
#: The two softmaxes agree to fp32 rounding, but the model casts attention
#: weights to bf16 (8 significant bits, step 2**-8 ≈ 0.004 relative), so a
#: rounding that lands on the other side of a bf16 step changes a weight by
#: one step, and 16 bf16 layers carry that into the logits (std ≈ 0.9 at
#: random init).  0.1 is about 25 such steps at |logit| = 1; a wrong kernel
#: (unnormalised or shifted rows) moves logits by O(1).
LOGITS_ATOL = 0.1
#: Step-0 loss vs ln(vocab).  Random-init logits have std σ ≈ 0.9 (unit-
#: variance hidden state against a table of std d_model**-0.5), so the
#: expected cross-entropy is ln V + σ²/2 ≈ ln V + 0.4.
INIT_LOSS_TOL = 1.0
#: Step-0 loss, Pallas vs reference softmax: a mean over batch × seq tokens,
#: so the per-logit differences above average out; a wrong softmax moves it
#: by more than 0.1.
LOSS_ATOL = 0.02
#: Sharded vs one-device losses: the same bf16 matmuls summed in another
#: order across 4 devices; 0.02 is 0.2% of a loss near 11.
SHARDED_LOSS_ATOL = 0.02


@dataclass(frozen=True)
class Plan:
    """What one run drives.  ``FULL`` is what the script runs on the chip;
    tests hand :func:`run` a tiny plan on the CPU."""
    platform: str = "tpu"
    arch: str = "olmo-1b"
    variant: str = "full"
    softmax_impl: str = "auto"       # "auto" picks the Pallas kernel on a TPU
    seed: int = 0
    batch: int = 8
    prompt_len: int = 512
    max_len: int = 1024
    gen: int = 32
    temperature: float = 0.8
    train_layers: int = 8
    train_batch: int = 8
    train_seq: int = 512
    train_steps: int = 3
    mesh: tuple[int, int] = (2, 2)   # (data, model) for --chips 4


FULL = Plan()


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"[check] ok: {what}", flush=True)


def _memory(device, key: str):
    """A ``memory_stats()`` entry; None where the backend reports none."""
    return (device.memory_stats() or {}).get(key)


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def _check_prefill(plan: Plan, cfg, params, prompts) -> None:
    cache = make_cache(cfg, plan.batch, plan.max_len)
    toks = jnp.asarray(prompts)
    t0 = time.perf_counter()
    compiled = jax.jit(make_prefill(cfg)).lower(params, cache, toks).compile()
    print(f"[serve] prefill compile {time.perf_counter() - t0:.2f} s",
          flush=True)
    if plan.platform == "tpu":
        n = compiled.as_text().count("tpu_custom_call")
        check(n > 0, f"compiled prefill holds {n} tpu_custom_call "
                     "(the COPIFT softmax runs as a Pallas kernel)")
    logits = compiled(params, cache, toks)[0]
    ref_cfg = cfg.replace(softmax_impl="reference")
    ref = jax.jit(make_prefill(ref_cfg))(params, cache, toks)[0]
    check(logits.shape == (plan.batch, cfg.vocab_size),
          f"prefill logits have shape {logits.shape}")
    check(bool(jnp.all(jnp.isfinite(logits))), "prefill logits are finite")
    err = float(jnp.max(jnp.abs(logits - ref)))
    scale = float(jnp.std(ref))
    check(err <= LOGITS_ATOL,
          f"prefill logits vs reference softmax: max |diff| {err:.6f} <= "
          f"{LOGITS_ATOL} (reference logit std {scale:.4f})")


def _generate(plan: Plan, cfg, params, prompts, temperature: float):
    engine = ServeEngine(cfg, params, max_len=plan.max_len, batch=plan.batch,
                         temperature=temperature, seed=plan.seed)
    t0 = time.perf_counter()
    first = engine.generate(prompts, plan.gen).tokens
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = engine.generate(prompts, plan.gen).tokens
    steady = time.perf_counter() - t0
    label = "greedy" if temperature <= 0 else f"temperature {temperature}"
    new = plan.batch * plan.gen
    print(f"[serve] {label}: first call {cold:.3f} s (compiles), steady "
          f"{steady:.3f} s for {new} new tokens = {new / steady:.1f} tokens/s "
          f"(prefill of {plan.batch}x{plan.prompt_len} included)", flush=True)
    check(first.shape == (plan.batch, plan.prompt_len + plan.gen)
          and bool(np.all((first >= 0) & (first < cfg.vocab_size))),
          f"{label}: {first.shape} tokens inside the vocabulary")
    check(np.array_equal(first, again), f"{label}: decoding is deterministic")
    return first


def _check_prng(vocab: int) -> None:
    for kind in ("xoshiro128p", "lcg"):
        for seed in (0, 0x9E3779B9, 2**32 - 1):
            got = np.asarray(kops.uniform(seed, (vocab,), kind=kind,
                                          impl="pallas"))
            want = np.asarray(kops.uniform(seed, (vocab,), kind=kind,
                                           impl="reference"))
            check(np.array_equal(got, want),
                  f"Pallas {kind} uniforms (seed {seed:#x}, {vocab} wide) "
                  "equal uniform_counter_ref bit for bit")


def serve_phase(plan: Plan) -> None:
    cfg = load_config(plan.arch, plan.variant).replace(
        softmax_impl=plan.softmax_impl)
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}; batch {plan.batch} x prompt "
          f"{plan.prompt_len}, max_len {plan.max_len}, {plan.gen} new tokens",
          flush=True)
    params = init_params(cfg, jax.random.PRNGKey(plan.seed))
    prompts = np.random.default_rng(plan.seed).integers(
        0, cfg.vocab_size, (plan.batch, plan.prompt_len)).astype(np.int32)
    _check_prefill(plan, cfg, params, prompts)
    greedy = _generate(plan, cfg, params, prompts, 0.0)
    sampled = _generate(plan, cfg, params, prompts, plan.temperature)
    check(not np.array_equal(greedy, sampled),
          "sampled tokens differ from greedy ones")
    _check_prng(cfg.vocab_size)


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------

def train_phase(plan: Plan) -> None:
    vocab = load_config(plan.arch, plan.variant).vocab_size
    common = ["--arch", plan.arch, "--variant", plan.variant,
              "--layers", str(plan.train_layers),
              "--batch", str(plan.train_batch), "--seq", str(plan.train_seq),
              "--seed", str(plan.seed), "--log-every", "1"]
    hist = train.main(common + ["--steps", str(plan.train_steps),
                                "--softmax-impl", plan.softmax_impl])
    ref = train.main(common + ["--steps", "1", "--softmax-impl", "reference"])
    losses = [h["loss"] for h in hist]
    secs = [h["seconds"] for h in hist]
    steady = float(np.mean(secs[1:])) if len(secs) > 1 else float("nan")
    tokens = plan.train_batch * plan.train_seq
    print(f"[train] losses {losses}; step 0 {secs[0]:.3f} s (compiles), "
          f"steady {steady:.3f} s/step = {tokens / steady:.1f} tokens/s",
          flush=True)
    check(len(losses) == plan.train_steps
          and all(math.isfinite(v) for v in losses),
          f"{plan.train_steps} training losses are finite")
    check(abs(losses[0] - math.log(vocab)) <= INIT_LOSS_TOL,
          f"step-0 loss {losses[0]:.4f} within {INIT_LOSS_TOL} of "
          f"ln({vocab}) = {math.log(vocab):.4f}")
    check(abs(losses[0] - ref[0]["loss"]) <= LOSS_ATOL,
          f"step-0 loss {losses[0]:.6f} vs reference softmax "
          f"{ref[0]['loss']:.6f} within {LOSS_ATOL}")


# ---------------------------------------------------------------------------
# four chips: sharded train step vs one device
# ---------------------------------------------------------------------------

def _steps(step, state, batches, label: str) -> list[float]:
    losses = []
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        print(f"[sharded] {label} step {i}: loss {loss:.6f} "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        losses.append(loss)
    return losses


def sharded_phase(plan: Plan) -> None:
    cfg = load_config(plan.arch, plan.variant).replace(
        softmax_impl=plan.softmax_impl).with_depth(plan.train_layers)
    shape = ShapeConfig("chip_smoke", plan.train_seq, plan.train_batch, "train")
    mesh = make_mesh(plan.mesh, ("data", "model"))
    rules = ShardingRules(cfg, mesh, shape)
    print(f"[sharded] {cfg.name} depth cut to {cfg.n_layers} layers on mesh "
          f"{dict(mesh.shape)}: fsdp={rules.fsdp} tp={rules.use_tp} "
          f"dp axes {rules.dp_axes}", flush=True)
    pipe = TokenPipeline(cfg, shape, PipelineConfig(seed=plan.seed + 1))
    batches = [pipe.host_batch_at(s) for s in range(plan.train_steps)]

    def init():
        return init_train_state(cfg, init_params(cfg, jax.random.PRNGKey(plan.seed)))

    fn, _, (state_sh, batch_sh) = step_and_specs(cfg, shape, rules, mesh)
    with jax.set_mesh(mesh):
        state = jax.jit(init, out_shardings=state_sh)()
        held = {d: 0 for d in mesh.devices.flat}
        for leaf in jax.tree.leaves(state):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
        in_use = [_memory(d, "bytes_in_use") for d in held]
        print(f"[sharded] train state {total} bytes; held per device "
              f"{list(held.values())}; bytes_in_use {in_use}", flush=True)
        largest = max(held.values()) / total
        check(largest <= 1.2 / len(held),
              f"train state spread over {len(held)} devices: the largest "
              f"share is {largest:.3f} of it")
        step = jax.jit(fn, out_shardings=(state_sh, None), donate_argnums=0)
        sharded = _steps(step, state,
                         [jax.device_put(b, batch_sh) for b in batches],
                         "sharded")
    del state
    one = _steps(train.jit_train_step(cfg, AdamWConfig()), jax.jit(init)(),
                 batches, "one device")
    diff = max(abs(a - b) for a, b in zip(sharded, one))
    check(all(math.isfinite(v) for v in sharded + one) and
          diff <= SHARDED_LOSS_ATOL,
          f"sharded losses {sharded} match one device {one}: max |diff| "
          f"{diff:.6f} <= {SHARDED_LOSS_ATOL}")


# ---------------------------------------------------------------------------

def run(plan: Plan, chips: int = 1) -> dict:
    """Run the phases for ``chips`` and return the result line's object."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != plan.platform:
        raise SystemExit(f"no TPU found: JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) != chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices; JAX sees "
                         f"{len(devices)}")
    enable_compile_cache()
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    t0 = time.perf_counter()
    if chips == 1:
        serve_phase(plan)
        print(f"[serve] peak bytes in use so far "
              f"{_memory(dev, 'peak_bytes_in_use')}", flush=True)
        train_phase(plan)
        print(f"[train] peak bytes in use so far "
              f"{_memory(dev, 'peak_bytes_in_use')}", flush=True)
    else:
        sharded_phase(plan)
    print(f"[done] all phases {time.perf_counter() - t0:.1f} s", flush=True)
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(devices)}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    result = run(FULL, args.chips)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
