"""Batched serving driver: prefill a batch of prompts, decode with KV cache.

CPU-runnable on reduced configs; the full-scale serve_step for the
production mesh is lowered by launch/dryrun.py (decode_32k / long_500k).

Run:  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --gen 32
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import base as cfgbase
from repro.core import machine
from repro.models import transformer as TF
from repro.serve import decode as SD


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=48)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--long-context", action="store_true",
                    help="sliding-window ring cache instead of full cache")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    machine.use_compile_cache()
    cfg = cfgbase.get(args.arch).reduced()
    params = TF.init_params(jax.random.PRNGKey(args.seed), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    total = args.prompt_len + args.gen
    cache_len = SD.cache_len_for(cfg, total, long_context=args.long_context)
    cache = TF.init_cache(cfg, args.batch, cache_len)

    kw = {}
    if cfg.enc_dec:
        frames = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, 32, cfg.d_model), cfg.dtype()
        )
        kw["memory"] = TF.encode(params, cfg, frames)

    print(
        f"arch={cfg.arch_id} batch={args.batch} cache_len={cache_len} "
        f"({'sliding-window' if args.long_context else 'full'})"
    )
    t0 = time.perf_counter()
    toks = SD.generate(
        params, cfg, prompt, cache,
        steps=args.gen, key=jax.random.PRNGKey(args.seed + 2),
        temperature=args.temperature, **kw,
    )
    dt = time.perf_counter() - t0
    print(f"generated {toks.shape} in {dt:.1f}s = {args.batch * args.gen / dt:.1f} tok/s")
    print("first sequence:", toks[0, :16].tolist(), "...")


if __name__ == "__main__":
    main()
