"""Serving driver: loads (or initializes) params for --arch and decodes a
batch of synthetic prompts through the ServeEngine (prefill + step loop).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --variant smoke \
      --batch 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.configs import load_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as ckpt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--variant", choices=["full", "smoke"], default="smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--params", default="", help="optional checkpoint path")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = load_config(args.arch, args.variant)
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: it attends both ways "
                         "over the whole input, so it has no decode step")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.params:
        params, _ = ckpt.load(args.params, like=params)

    engine = ServeEngine(cfg, params, max_len=args.prompt_len + args.gen + 1,
                         batch=args.batch, temperature=args.temperature,
                         seed=args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    result = engine.generate(prompts, args.gen)
    dt = time.time() - t0
    tps = args.batch * args.gen / dt
    print(f"[serve] {cfg.name}: {args.batch}×{args.gen} tokens in "
          f"{dt:.2f}s ({tps:.1f} tok/s)")
    print("sample:", result.tokens[0, args.prompt_len:args.prompt_len + 16])
    return result


if __name__ == "__main__":
    main()
