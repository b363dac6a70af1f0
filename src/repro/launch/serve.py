"""Serving driver: batched requests against any --arch (reduced presets on
CPU; full configs are exercised via the dry-run).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.train import preset_config
from repro.models import lm
from repro.serve import ServeEngine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--preset", default="small",
                    choices=["smoke", "small", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = preset_config(get_config(args.arch), args.preset)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, batch_size=args.requests,
                         max_len=args.prompt_len + args.max_new,
                         temperature=args.temperature, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, args.prompt_len)
                    .astype(np.int32), max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    t0 = time.time()
    done = engine.run_batch(reqs)
    dt = time.time() - t0
    stats = engine.throughput_stats(done, dt)
    print(f"arch={cfg.name} ({lm.param_count(params)/1e6:.1f}M params)")
    print(f"served {stats['requests']} requests, "
          f"{stats['new_tokens']} new tokens in {dt:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s)")
    for i, r in enumerate(done[:3]):
        print(f"  req{i}: prompt[:8]={r.prompt[:8].tolist()} "
              f"-> out[:8]={r.out_tokens[:8]}")


if __name__ == "__main__":
    main()
