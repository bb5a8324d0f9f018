"""Serving launcher: batched generation with the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --batch 4 --prompt-len 16 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

import repro.configs as cfgs
from repro.launch.device import banner, use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime.dist import make_dist
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=cfgs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--impl", default=None)
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size in token positions")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt positions fed per engine step")
    args = ap.parse_args(argv)

    use_compile_cache()
    print(banner())
    cfg = cfgs.smoke_config(args.arch) if args.smoke else cfgs.get_config(args.arch)
    api = build_model(cfg)
    mesh = make_host_mesh()
    dist = make_dist(mesh, impl=args.impl)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, max_batch=args.batch,
                      max_seq=args.prompt_len + args.new_tokens + 8, dist=dist,
                      block_size=args.block_size,
                      prefill_chunk=args.prefill_chunk)

    rng = np.random.default_rng(0)
    reqs = [
        Request(i, rng.integers(1, cfg.vocab_size, args.prompt_len).astype(np.int32),
                max_new_tokens=args.new_tokens, temperature=args.temperature)
        for i in range(args.batch)
    ]
    t0 = time.time()
    eng.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    print(f"arch={cfg.name} impl={dist.abi.backend.name}: {args.batch} requests, "
          f"{total_new} tokens in {dt:.2f}s ({total_new/dt:.1f} tok/s)")
    print(f"  stats: {eng.stats}")
    if eng.paged:
        print(f"  kv pool: {eng.alloc.live_blocks} live / "
              f"{eng.alloc.num_blocks - 1} blocks of {eng.block_size}")
    for r in reqs[:2]:
        print(f"  req{r.rid}: {r.out_tokens[:12]}")
    return reqs


if __name__ == "__main__":
    main()
