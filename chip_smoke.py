#!/usr/bin/env python3
"""Chip smoke test: qwen2-0.5b at its published widths through the serving
engine and the ZeRO-1 trainer, on TPU.

    python chip_smoke.py             # one chip: serve, then train
    python chip_smoke.py --chips 4   # four chips: dp=4 ZeRO-1 training on
                                     # lax collectives vs the int8 ring wire
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke [--chips 4]
                                     # reduced widths on any platform
                                     # (--chips 4 on the CPU also needs
                                     # XLA_FLAGS=--xla_force_host_platform_device_count=4)

Every phase runs in this one process (a chip belongs to one process), goes
through the library's own entry points, checks its results and raises on
any failure.  Each phase prints one JSON line of what it ran and measured;
the last line is ``{"ok": true, "device": {...}}``.  Without a TPU (and
without ``--smoke``), or without the device count the phase needs, the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen2-0.5b"

#: relative error bound of the int8 ring wire on a reduced gradient — the
#: bound the multidev battery holds the fused-hop ZeRO-1 round trip to
#: (tests/multidev_battery.py, section 12)
INT8_WIRE_REL = 0.05

#: first-token check: a top-2 logit margin counts as decided when it exceeds
#: this many bf16 ulps of the top logit
MARGIN_ULPS = 4


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def memory() -> list:
    """Per-device ``bytes_in_use`` and ``peak_bytes_in_use`` (None where the
    backend keeps no statistics)."""
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id, "in_use": st.get("bytes_in_use"),
                    "peak": st.get("peak_bytes_in_use")})
    return out


def widths(cfg) -> dict:
    return {"arch": cfg.name, "layers": cfg.num_layers, "d_model": cfg.d_model,
            "d_ff": cfg.d_ff, "heads": cfg.num_heads,
            "kv_heads": cfg.num_kv_heads, "vocab": cfg.vocab_size}


def n_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def bf16_ulp(x: float) -> float:
    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)


# ---------------------------------------------------------------------------
# serving: continuous batching on the paged path vs the one-at-a-time oracle
# ---------------------------------------------------------------------------
def serve_phase(cfg, clog, *, seed: int, n_req: int, prompt_len: int,
                new_tokens: int) -> None:
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime.dist import make_dist
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.scheduler import DECODE

    api = build_model(cfg)
    dist = make_dist(make_host_mesh())
    params = api.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]

    def engine():
        return ServeEngine(api, params, max_batch=n_req,
                           max_seq=prompt_len + new_tokens + 8, dist=dist)

    eng = engine()
    assert eng.paged, "qwen2 serves on the paged path"
    reqs = [Request(i, p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    c0 = clog.mark()
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    steady = None
    while eng.has_work:
        # the steady window opens once every slot decodes and one decode
        # step (the warm-up that compiles it) is behind us
        if steady is None and eng.stats["decode_steps"] > 0 and all(
                s is not None and s.state == DECODE
                for s in eng.scheduler.slots):
            steady = (clog.mark(), time.perf_counter(),
                      eng.stats["decode_steps"])
        eng.step()
    t1 = time.perf_counter()
    c1 = clog.mark()
    if steady is None:
        raise RuntimeError("serve: no step ran with every slot decoding")
    (sc, ss), ts, sd = steady
    window_steps = eng.stats["decode_steps"] - sd
    window_compiles = c1[0] - sc
    got = [list(r.out_tokens) for r in reqs]
    if any(len(g) != new_tokens for g in got):
        raise RuntimeError(f"serve: short streams {[len(g) for g in got]}")

    # reference 1: a fresh engine serving one request at a time
    ref = engine()
    want = [list(ref.generate(p, max_new_tokens=new_tokens)) for p in prompts]
    mismatched = [i for i in range(n_req) if got[i] != want[i]]
    if mismatched:
        raise RuntimeError(f"serve: batched != one-at-a-time for {mismatched}")

    # reference 2: the first token is the argmax of a plain full-sequence
    # forward wherever the top-2 margin clears bf16 noise
    last = jax.jit(lambda p, t: api.forward(p, {"tokens": t})[0][:, -1]
                   .astype(jnp.float32))(params, jnp.asarray(np.stack(prompts)))
    last = np.asarray(last)
    checked, margins = 0, []
    for i in range(n_req):
        top2 = np.sort(last[i])[-2:]
        margin = float(top2[1] - top2[0])
        margins.append(margin)
        if margin > MARGIN_ULPS * bf16_ulp(float(top2[1])):
            checked += 1
            if int(np.argmax(last[i])) != got[i][0]:
                raise RuntimeError(
                    f"serve: request {i} first token {got[i][0]} != forward "
                    f"argmax {int(np.argmax(last[i]))} (margin {margin})")

    emit(phase="serve", **widths(cfg), params=n_params(params),
         requests=n_req, prompt_len=prompt_len, new_tokens=new_tokens,
         tokens=sum(len(g) for g in got),
         warmup_compiles=sc - c0[0], warmup_compile_s=ss - c0[1],
         window_decode_steps=window_steps, window_compiles=window_compiles,
         window_s=t1 - ts, total_s=t1 - t0,
         oracle_match=True, first_token_checked=checked,
         first_token_margins=margins, memory=memory())
    if window_compiles:
        raise RuntimeError(f"serve: {window_compiles} compiles while decoding")
    eng.decode_sync.free()
    ref.decode_sync.free()


# ---------------------------------------------------------------------------
# training: init_state + the ZeRO-1 step under run_supervised
# ---------------------------------------------------------------------------
def train_run(cfg, clog, *, impl, seed: int, steps: int, global_batch: int,
              seq_len: int, aot: bool = False) -> dict:
    """``steps`` supervised ZeRO-1 steps on a mesh over every device.

    With ``aot`` the step is lowered and compiled before the loop, and the
    compiled program runs the steps (its text is returned); otherwise the
    loop calls the jitted step, as the training launcher does."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.data.pipeline import DataPipeline, SyntheticSource
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig, warmup_cosine
    from repro.runtime.dist import make_dist
    from repro.runtime.fault import run_supervised
    from repro.train import train_loop

    api = build_model(cfg)
    dist = make_dist(make_host_mesh(), impl=impl,
                     sequence_parallel=cfg.parallelism.sequence_parallel,
                     compression=cfg.parallelism.grad_compression)
    c0 = clog.mark()
    state = train_loop.init_state(api, jax.random.PRNGKey(seed), dist=dist)
    jax.block_until_ready(state)
    mem_init, n = memory(), n_params(state.params)
    schedule = lambda step: warmup_cosine(step, warmup=1, total=steps)
    jstep = jax.jit(train_loop.make_train_step(
        api, dist, AdamWConfig(lr=3e-4), schedule=schedule),
        donate_argnums=(0,))

    pipe = DataPipeline(SyntheticSource(cfg.vocab_size, seed=seed),
                        global_batch=global_batch, seq_len=seq_len)
    cache = {}

    def get_batch(i):
        if i not in cache:
            cache.clear()
            cache[i] = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        return cache[i]

    text = None
    if aot:
        compiled = jstep.lower(state, get_batch(0)).compile()
        text = compiled.as_text()
        jstep = compiled
    per_step, gnorms, secs = [], [], []

    def counted(s, b):
        n0, t = clog.count, time.perf_counter()
        out = jax.block_until_ready(jstep(s, b))
        secs.append(time.perf_counter() - t)
        per_step.append(clog.count - n0)
        gnorms.append(float(out[1].grad_norm))
        return out

    ckdir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        report = run_supervised(
            counted, state, get_batch, checkpointer=Checkpointer(ckdir),
            total_steps=steps, checkpoint_every=steps, state_like=state)
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    c1 = clog.mark()
    losses = [float(x) for x in report.losses]
    if report.restarts:
        raise RuntimeError(f"train[{impl}]: {report.restarts} restarts")
    if len(losses) != steps or not np.all(np.isfinite(losses + gnorms)):
        raise RuntimeError(f"train[{impl}]: losses {losses} gnorms {gnorms}")
    out = dict(impl=impl or dist.abi.backend.name, dp=dist.dp_size,
               params=n, steps=steps,
               global_batch=global_batch, seq_len=seq_len, losses=losses,
               grad_norms=gnorms, step_s=secs, compiles_per_step=per_step,
               compiles=c1[0] - c0[0], compile_s=c1[1] - c0[1],
               restarts=report.restarts, memory_init=mem_init,
               memory=memory(), text=text, dist=dist,
               opt_m=report.final_state.opt.m)
    return out


def train_phase(cfg, clog, *, seed: int, steps: int, global_batch: int,
                seq_len: int) -> None:
    r = train_run(cfg, clog, impl=None, seed=seed, steps=steps,
                  global_batch=global_batch, seq_len=seq_len)
    steady = r["compiles_per_step"][1:]
    emit(phase="train", **widths(cfg),
         **{k: v for k, v in r.items() if k not in ("text", "dist", "opt_m")})
    if any(steady):
        raise RuntimeError(f"train: compiles in steps >= 2: {steady}")


def zero1_dp4_phase(cfg, clog, *, seed: int, steps: int, global_batch: int,
                    seq_len: int) -> None:
    """dp=4 ZeRO-1: the same seed and data through lax collectives (paxi)
    and the fused int8 ring wire (ring-int8)."""
    runs = {}
    for impl in ("paxi", "ring-int8"):
        r = train_run(cfg, clog, impl=impl, seed=seed, steps=steps,
                      global_batch=global_batch, seq_len=seq_len, aot=True)
        dist, text, m = r.pop("dist"), r.pop("text"), r.pop("opt_m")
        # optimizer state sharded: one moment shard of padded/dp per device
        shards = {s.device.id: s.data.shape[0] for s in m.addressable_shards}
        if len(shards) != dist.dp_size or len(set(shards.values())) != 1 \
                or next(iter(shards.values())) * dist.dp_size != m.shape[0]:
            raise RuntimeError(f"train[{impl}]: moments not sharded: {shards}")
        r["moment_shard_elems"] = shards
        caps = dist.abi.capabilities()["reduce_scatter"].get("wire_kernel")
        r["wire_kernel"] = caps
        r["zero1_pack"] = dist.zero1_plans.wire_kernel
        r["tpu_custom_calls"] = text.count("tpu_custom_call")
        if impl == "ring-int8":
            if caps != "pallas":
                raise RuntimeError(f"ring-int8 wire_kernel = {caps!r}")
            # beyond the kernels paxi's step holds (the ZeRO-1 pack), the
            # fused reduce-scatter: one quantize, dp-2 middle hops, one
            # final hop
            hops = r["hop_kernels"] = (r["tpu_custom_calls"]
                                       - runs["paxi"]["tpu_custom_calls"])
            if jax.devices()[0].platform == "tpu" and hops != dist.dp_size:
                raise RuntimeError(f"ring-int8 step holds {hops} hop "
                                   f"kernels, want {dist.dp_size}")
        emit(phase="train-dp4", **widths(cfg), **r)
        runs[impl] = r
        del dist, m

    lp, lr = runs["paxi"]["losses"], runs["ring-int8"]["losses"]
    gp, gr = runs["paxi"]["grad_norms"], runs["ring-int8"]["grad_norms"]
    # the int8 wire perturbs each reduced gradient by at most INT8_WIRE_REL
    # (relative); to first order a loss step moves by the same share, so
    # the two series may part by that share of the distance paxi's loss
    # has travelled, plus one bf16 ulp of the loss itself (bf16 parameters
    # round updates that differ in the last bits)
    bounds, diffs = [], []
    for k in range(steps):
        travel = sum(abs(lp[j] - lp[j - 1]) for j in range(1, k + 1))
        bounds.append(INT8_WIRE_REL * travel + 2.0 ** -8 * abs(lp[k]))
        diffs.append(abs(lr[k] - lp[k]))
    gdiff = abs(gr[0] - gp[0]) / max(abs(gp[0]), 1e-30)
    ok = all(d <= b for d, b in zip(diffs, bounds)) and gdiff <= INT8_WIRE_REL
    emit(phase="train-dp4-compare", losses_paxi=lp, losses_ring_int8=lr,
         loss_diff=diffs, loss_bound=bounds, grad_norm0_rel_diff=gdiff,
         grad_norm0_bound=INT8_WIRE_REL, agree=ok)
    if not ok:
        raise RuntimeError("paxi and ring-int8 losses part beyond the bound")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced widths; runs on any platform")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if not args.smoke and devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: needs {args.chips} devices, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2

    import repro.configs as cfgs
    from repro.launch.device import (CompileLog, banner, device_info,
                                     use_compile_cache)

    cache_dir = use_compile_cache()
    print(banner(), f"compile_cache={cache_dir}", flush=True)
    clog = CompileLog()
    cfg = cfgs.smoke_config(ARCH) if args.smoke else cfgs.get_config(ARCH)
    if args.smoke:
        serve = dict(n_req=4, prompt_len=16, new_tokens=8)
        train = dict(steps=4, global_batch=8 * args.chips, seq_len=16)
    else:
        serve = dict(n_req=4, prompt_len=128, new_tokens=32)
        train = dict(steps=4, global_batch=8 * args.chips, seq_len=512)

    t0 = time.perf_counter()
    if args.chips == 4:
        zero1_dp4_phase(cfg, clog, seed=args.seed, **train)
    else:
        serve_phase(cfg, clog, seed=args.seed, **serve)
        gc.collect()  # the engines hold reference cycles; free their HBM
        train_phase(cfg, clog, seed=args.seed, **train)
    emit(phase="done", compiles=clog.count, compile_s=clog.seconds,
         wall_s=time.perf_counter() - t0)
    clog.close()
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
