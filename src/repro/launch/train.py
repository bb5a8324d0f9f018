"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --global-batch 8 --seq-len 128 --smoke \
        --ckpt-dir /tmp/ckpt --impl paxi

``--smoke`` selects the reduced config (CPU-runnable); otherwise the full
assigned config is used (TPU-scale).  The loop runs under the fault-
tolerance supervisor: periodic async checkpoints, restart-on-failure,
straggler watchdog.  ``--impl`` picks the ABI backend (the paper's
recompile-free implementation swap).
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as cfgs
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataPipeline, SyntheticSource
from repro.launch.device import banner, use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, warmup_cosine
from repro.runtime.dist import make_dist
from repro.runtime.fault import run_supervised
from repro.train import train_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=cfgs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--impl", default=None, help="PAX ABI backend")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    use_compile_cache()
    print(banner())
    cfg = cfgs.smoke_config(args.arch) if args.smoke else cfgs.get_config(args.arch)
    api = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.model_axis))
    dist = make_dist(mesh, impl=args.impl,
                     sequence_parallel=cfg.parallelism.sequence_parallel,
                     compression=cfg.parallelism.grad_compression)
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M mesh={dict(mesh.shape)} "
          f"impl={dist.abi.backend.name} mode={cfg.parallelism.grad_sync}")

    key = jax.random.PRNGKey(0)
    # dist activates the ZeRO-1 flat optimizer layout in abi mode
    state = train_loop.init_state(api, key, dist=dist)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
    print(f"actual params: {n_params/1e6:.2f}M")

    schedule = lambda step: warmup_cosine(step, warmup=args.warmup, total=args.steps)
    # the state is donated: the step writes its new state over the old one,
    # so the device holds one copy of the optimizer state, not two
    step_fn = jax.jit(train_loop.make_train_step(
        api, dist, AdamWConfig(lr=args.lr), schedule=schedule),
        donate_argnums=(0,))

    pipe = DataPipeline(SyntheticSource(cfg.vocab_size, seed=0),
                        global_batch=args.global_batch, seq_len=args.seq_len)
    cache = {}

    def get_batch(i):
        # cache recent batches so restarts can replay the same step's data
        if i not in cache:
            cache.clear()
            b = next(pipe)
            cache[i] = {k: jnp.asarray(v) for k, v in b.items()}
        return cache[i]

    ckpt = Checkpointer(args.ckpt_dir, keep=3)
    t0 = time.time()
    last = {"t": t0, "step": 0}

    raw_step = step_fn

    def logged_step(state, batch):
        out = raw_step(state, batch)
        s = int(out[0].step)
        if s % args.log_every == 0:
            dt = (time.time() - last["t"]) / max(s - last["step"], 1)
            toks = args.global_batch * args.seq_len / max(dt, 1e-9)
            print(f"step {s:5d} loss {float(out[1].loss):.4f} "
                  f"gnorm {float(out[1].grad_norm):.3f} {dt*1e3:.0f} ms/step "
                  f"({toks:,.0f} tok/s)")
            last["t"], last["step"] = time.time(), s
        return out

    report = run_supervised(
        logged_step, state, get_batch, checkpointer=ckpt,
        total_steps=args.steps, checkpoint_every=args.ckpt_every,
        state_like=state)
    dt = time.time() - t0
    print(f"done: {report.steps_completed} steps in {dt:.1f}s "
          f"({report.restarts} restarts, {report.stragglers} stragglers); "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")
    return report


if __name__ == "__main__":
    main()
