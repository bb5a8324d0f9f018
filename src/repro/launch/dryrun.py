import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count and the platform at first initialization).  This module is the ONLY
# place the 512 placeholder devices exist; tests/benches see the real device
# count.  The placeholders are CPU devices, and the --all children inherit
# the setting: a dry run never takes a chip.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production meshes, print memory_analysis() and
cost_analysis(), and record the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh pod1
    PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, subprocess each
    PYTHONPATH=src python -m repro.launch.dryrun --list

Success here proves the distribution config is coherent: sharding
mismatches, compile-time OOM, or unsupported collectives are bugs.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.configs as cfgs
from repro.launch.hlo_analysis import roofline_from_compiled, collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models.model import analytic_param_count, model_flops_per_token
from repro.optim.adamw import AdamWConfig
from repro.runtime.dist import make_dist
from repro.runtime.sharding import use_rules
from repro.train import train_loop

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def _apply_env_overrides(cfg):
    """Hillclimb knobs (EXPERIMENTS.md §Perf): each hypothesis->change cycle
    re-runs a cell under PAX_OVERRIDE_* without touching the baseline config.

      PAX_OVERRIDE_ATTENTION=blockwise|xla
      PAX_OVERRIDE_MICROBATCH=<int>
      PAX_OVERRIDE_REMAT=none|dots|full
      PAX_OVERRIDE_CAPACITY=<float>        (MoE capacity factor)
      PAX_OVERRIDE_COMPRESSION=bf16|int8   (dp grad sync wire)
      PAX_OVERRIDE_SEQPAR=0|1
    """
    par = cfg.parallelism
    if os.environ.get("PAX_OVERRIDE_ATTENTION"):
        cfg = dataclasses.replace(cfg, attention_impl=os.environ["PAX_OVERRIDE_ATTENTION"])
    if os.environ.get("PAX_OVERRIDE_MICROBATCH"):
        par = dataclasses.replace(par, microbatch=int(os.environ["PAX_OVERRIDE_MICROBATCH"]))
    if os.environ.get("PAX_OVERRIDE_REMAT"):
        par = dataclasses.replace(par, remat=os.environ["PAX_OVERRIDE_REMAT"])
    if os.environ.get("PAX_OVERRIDE_COMPRESSION"):
        par = dataclasses.replace(par, grad_compression=os.environ["PAX_OVERRIDE_COMPRESSION"])
    if os.environ.get("PAX_OVERRIDE_SEQPAR"):
        par = dataclasses.replace(par, sequence_parallel=bool(int(os.environ["PAX_OVERRIDE_SEQPAR"])))
    if par is not cfg.parallelism:
        cfg = dataclasses.replace(cfg, parallelism=par)
    if os.environ.get("PAX_OVERRIDE_CAPACITY") and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(os.environ["PAX_OVERRIDE_CAPACITY"])))
    return cfg


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _sanitize_spec(spec: P, mesh) -> P:
    """Drop axes not present in this mesh (e.g. 'pod' on the single-pod
    mesh — cache/state specs name the superset of axes)."""
    names = set(mesh.axis_names)
    parts = []
    for p in tuple(spec):
        if p is None:
            parts.append(None)
        elif isinstance(p, tuple):
            kept = tuple(a for a in p if a in names)
            parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            parts.append(p if p in names else None)
    return P(*parts)


def _tree_sds(struct_tree, spec_tree, mesh):
    def one(s, spec):
        if not isinstance(spec, P):
            spec = P()
        spec = _sanitize_spec(_trim(spec, len(s.shape)), mesh)
        # drop uneven dims (e.g. kv_heads=2 over model=16): replicate instead
        parts = []
        for dim, p in zip(s.shape, tuple(spec)):
            if p is not None:
                import math as _m

                size = (_m.prod(mesh.shape[a] for a in p) if isinstance(p, tuple)
                        else mesh.shape[p])
                if size <= 1 or dim % size != 0:
                    p = None
            parts.append(p)
        spec = P(*parts)
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, spec))

    return jax.tree.map(one, struct_tree, spec_tree,
                        is_leaf=lambda v: isinstance(v, P))


def _trim(spec: P, rank: int) -> P:
    parts = tuple(spec)
    if len(parts) > rank:
        parts = parts[:rank]
    return P(*parts)


def _drop_batch_axes(spec_tree, mesh):
    """For global_batch=1 cells the dp axes cannot shard the batch dim:
    replace ('pod','data') (or subsets) with None in cache/batch specs."""
    dp = {"pod", "data"}

    def fix(spec):
        if not isinstance(spec, P):
            return spec
        parts = []
        for p in tuple(spec):
            if p is None:
                parts.append(None)
            elif isinstance(p, tuple) and set(p) & dp:
                parts.append(None)
            elif p in dp:
                parts.append(None)
            else:
                parts.append(p)
        return P(*parts)

    return jax.tree.map(fix, spec_tree, is_leaf=lambda v: isinstance(v, P))


def batch_struct(cfg, shape, mesh, dp_axes):
    b, s = shape.global_batch, shape.seq_len
    bspec = P(dp_axes) if b % _axes_size(mesh, dp_axes) == 0 and b >= _axes_size(mesh, dp_axes) else P()
    out = {
        "tokens": _sds((b, s), jnp.int32, mesh, bspec),
        "targets": _sds((b, s), jnp.int32, mesh, bspec),
    }
    if cfg.family == "encdec":
        out["frames"] = _sds((b, cfg.encdec.encoder_frames, cfg.d_model), jnp.bfloat16,
                             mesh, bspec)
    if cfg.family == "vlm":
        out["patches"] = _sds((b, cfg.vlm.num_patches, cfg.vlm.patch_embed_dim),
                              jnp.bfloat16, mesh, bspec)
    return out


def _axes_size(mesh, axes):
    import math

    return math.prod(mesh.shape[a] for a in axes)


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, multi_pod: bool, impl: str = "paxi",
               unroll: bool = False, layer_override: int = 0):
    """One lowering of one cell.

    ``unroll=False`` (the deployable graph): scan-over-layers + grad
    accumulation — gives the true ``memory_analysis`` and proves the
    sharding compiles.  ``unroll=True`` (the accounting graph): layers
    unrolled and a SINGLE accumulation iteration (global_batch/n_micro)
    lowered, because XLA cost analysis does not multiply while-body
    FLOPs/bytes by trip count; roofline terms come from this graph
    (per-accumulation-iteration, with the once-per-step grad-sync tail
    included).  run_cell() combines both into one record.
    """
    cfg = _apply_env_overrides(cfgs.get_config(arch))
    shape = cfgs.SHAPES_BY_NAME[shape_name]
    n_micro = max(cfg.parallelism.microbatch, 1)
    if unroll:
        cfg = dataclasses.replace(
            cfg, parallelism=dataclasses.replace(
                cfg.parallelism, scan_layers=False, microbatch=1))
        if layer_override:
            cfg = dataclasses.replace(cfg, num_layers=layer_override)
        if shape.kind == "train" and n_micro > 1:
            # per-iteration batch, floored at the dp size so the accounting
            # graph keeps the batch sharded (a replicated batch would inflate
            # the TP collectives beyond anything the deployable graph does)
            dp = 32 if multi_pod else 16
            shape = dataclasses.replace(
                shape, global_batch=max(shape.global_batch // n_micro, dp))
    if shape.kind == "decode" and shape_name == "long_500k" and not cfg.supports_long_context:
        return {"status": "skipped",
                "reason": "full-attention arch; long_500k needs sub-quadratic attention"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    api = build_model(cfg)
    dist = make_dist(mesh, impl=impl,
                     sequence_parallel=cfg.parallelism.sequence_parallel,
                     compression=cfg.parallelism.grad_compression)
    mode = cfg.parallelism.grad_sync
    fsdp = ("pod", "data") if multi_pod else "data"
    key = jax.random.PRNGKey(0)

    if shape.kind == "train":
        state_struct = jax.eval_shape(lambda: train_loop.init_state(api, key))
        sspecs = train_loop.state_specs(api, mode, fsdp=fsdp, tp=dist.tp_axis)
        state_in = _tree_sds(state_struct, sspecs, mesh)
        batch_in = batch_struct(cfg, shape, mesh, dist.dp_axes)
        step_fn = train_loop.make_train_step(api, dist, AdamWConfig())
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        t0 = time.time()
        lowered = jitted.lower(state_in, batch_in)
        t_lower = time.time() - t0
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        params_struct = jax.eval_shape(api.init, key)
        pspecs = api.param_specs(fsdp=fsdp if mode == "gspmd" else None, tp=dist.tp_axis)
        params_in = _tree_sds(params_struct, pspecs, mesh)
        batch_in = batch_struct(cfg, shape, mesh, dist.dp_axes)

        last_only = bool(int(os.environ.get("PAX_OVERRIDE_PREFILL_LAST", "0")))

        def prefill_fn(params, batch):
            with use_rules(dist.rules):
                # §Perf it2: prefill needs one position's logits; last_only
                # slices the residual stream BEFORE the unembed matmul
                from repro.models import (encdec, hybrid, rwkv, transformer, vlm)
                mod = {"dense": transformer, "moe": transformer, "ssm": rwkv,
                       "hybrid": hybrid, "encdec": encdec, "vlm": vlm}[cfg.family]
                arg = batch if cfg.family in ("encdec", "vlm") else batch["tokens"]
                logits, _ = mod.forward(params, arg, cfg, dist, last_only=last_only)
                return logits[:, -1]

        t0 = time.time()
        lowered = jax.jit(prefill_fn).lower(params_in, batch_in)
        t_lower = time.time() - t0
        tokens = shape.global_batch * shape.seq_len
    else:  # decode
        params_struct = jax.eval_shape(api.init, key)
        pspecs = api.param_specs(fsdp=fsdp if mode == "gspmd" else None, tp=dist.tp_axis)
        params_in = _tree_sds(params_struct, pspecs, mesh)
        B = shape.global_batch
        if cfg.family == "encdec":
            # cache needs encoder frames: eval_shape through init_cache
            from repro.models import encdec as _encdec

            frames_s = jax.ShapeDtypeStruct(
                (B, cfg.encdec.encoder_frames, cfg.d_model), jnp.bfloat16)
            cache_struct = jax.eval_shape(
                lambda p, fr: _encdec.init_cache(p, fr, cfg, B, shape.seq_len),
                params_struct, frames_s)
        else:
            cache_struct = jax.eval_shape(lambda: api.decode_init(B, shape.seq_len))
        cspecs = api.cache_specs()
        if B < _axes_size(mesh, dist.dp_axes):
            cspecs = _drop_batch_axes(cspecs, mesh)
        cache_in = _tree_sds(cache_struct, cspecs, mesh)
        tok_spec = P(dist.dp_axes) if B % _axes_size(mesh, dist.dp_axes) == 0 else P()
        token_in = _sds((B, 1), jnp.int32, mesh, tok_spec)
        index_in = jax.ShapeDtypeStruct((), jnp.int32)

        def serve_step(params, token, cache, index):
            with use_rules(dist.rules):
                return api.decode_step(params, token, cache, index, dist)

        t0 = time.time()
        lowered = jax.jit(serve_step, donate_argnums=(2,)).lower(
            params_in, token_in, cache_in, index_in)
        t_lower = time.time() - t0
        tokens = shape.global_batch  # one token per sequence

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    n_active = analytic_param_count(cfg, active_only=True)
    flops_per_tok = model_flops_per_token(cfg)
    if shape.kind != "train":
        flops_per_tok //= 3  # forward only (no backward): 2*N*D
    model_flops = float(flops_per_tok) * tokens
    roof = roofline_from_compiled(compiled, chips, model_flops)
    stats = collective_bytes(compiled.as_text())

    result = {
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "mode": mode,
        "impl": impl,
        "unrolled": unroll,
        "accum_steps": n_micro,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "tokens_per_step": tokens,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
        },
        "collectives": {"bytes": stats.bytes_by_op, "count": stats.count_by_op},
        "roofline": roof.as_dict(),
    }
    return result


def _layer_period(cfg) -> int:
    return cfg.hybrid.shared_attn_every if cfg.hybrid is not None else 1


def run_cell(arch: str, shape_name: str, multi_pod: bool, impl: str = "paxi"):
    """Deployable (scan) compile for memory + exact roofline accounting.

    Accounting trick: per-layer cost is exactly linear in layer count (the
    stacks are homogeneous — hybrid archs are periodic with period
    ``shared_attn_every``), so instead of unrolling all L layers (hours for
    the 96-layer archs) we compile unrolled graphs at L1 and L2 reduced
    depths and extrapolate: total(L) = fixed + per_layer*(L) with
    per_layer = (m(L2)-m(L1))/(L2-L1).  FLOPs/bytes/collective bytes are
    all linear in L; memory_analysis comes from the deployable graph.
    """
    deploy = lower_cell(arch, shape_name, multi_pod, impl, unroll=False)
    if deploy.get("status") != "ok":
        return deploy
    cfg = cfgs.get_config(arch)
    L = cfg.num_layers
    period = _layer_period(cfg)
    if L <= 8 * period:
        acct = lower_cell(arch, shape_name, multi_pod, impl, unroll=True)
        if acct.get("status") == "ok":
            deploy["roofline"] = acct["roofline"]
            deploy["collectives"] = acct["collectives"]
            deploy["accounting"] = {"method": "full-unroll",
                                    "compile_s": acct["compile_s"],
                                    "tokens": acct["tokens_per_step"]}
        else:
            deploy["accounting_error"] = acct
        return deploy

    L1, L2 = 2 * period, 4 * period  # L=1 graphs fuse atypically; use 2/4
    acct1 = lower_cell(arch, shape_name, multi_pod, impl, unroll=True,
                       layer_override=L1)
    acct2 = lower_cell(arch, shape_name, multi_pod, impl, unroll=True,
                       layer_override=L2)
    if acct1.get("status") != "ok" or acct2.get("status") != "ok":
        deploy["accounting_error"] = (acct1 if acct1.get("status") != "ok" else acct2)
        return deploy

    def extrapolate(key):
        m1, m2 = acct1["roofline"][key], acct2["roofline"][key]
        per = (m2 - m1) / (L2 - L1)
        return max(m1 - per * L1 + per * L, 0.0)

    from repro.launch.hlo_analysis import Roofline

    # MODEL_FLOPS must use the FULL-depth config (acct graphs are shallow)
    fpt = model_flops_per_token(cfg)
    if cfgs.SHAPES_BY_NAME[shape_name].kind != "train":
        fpt //= 3
    model_flops = float(fpt) * acct1["tokens_per_step"]
    roof = Roofline(
        flops_per_device=extrapolate("flops_per_device"),
        hbm_bytes_per_device=extrapolate("hbm_bytes_per_device"),
        collective_bytes_per_device=extrapolate("collective_bytes_per_device"),
        chips=acct1["roofline"]["chips"],
        model_flops_global=model_flops,
    )
    coll = {}
    for op in set(acct1["collectives"]["bytes"]) | set(acct2["collectives"]["bytes"]):
        b1 = acct1["collectives"]["bytes"].get(op, 0)
        b2 = acct2["collectives"]["bytes"].get(op, 0)
        per = (b2 - b1) / (L2 - L1)
        coll[op] = int(max(b1 - per * L1 + per * L, 0))
    deploy["roofline"] = roof.as_dict()
    deploy["collectives"] = {"bytes": coll,
                             "count": acct2["collectives"]["count"]}
    deploy["accounting"] = {
        "method": f"layer-extrapolation L1={L1} L2={L2} -> L={L}",
        "compile_s": acct1["compile_s"] + acct2["compile_s"],
        "tokens": acct1["tokens_per_step"],
    }
    return deploy


ALL_MESHES = ("pod1", "pod2")


def iter_cells():
    for arch in cfgs.ARCH_NAMES:
        cfg = cfgs.get_config(arch)
        for shape in cfgs.shapes_for(cfg):
            yield arch, shape.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["pod1", "pod2"], default="pod1")
    ap.add_argument("--impl", default=os.environ.get("PAX_ABI_IMPL", "paxi"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)

    if args.list:
        for arch, shape in iter_cells():
            for m in ALL_MESHES:
                print(f"{arch} {shape} {m}")
        return

    if args.all:
        failures = 0
        for arch, shape in iter_cells():
            for m in ALL_MESHES:
                out = RESULTS_DIR / f"{arch}__{shape}__{m}.json"
                if out.exists() and json.loads(out.read_text()).get("status") in ("ok", "skipped"):
                    print(f"[cached] {arch} {shape} {m}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", m,
                       "--impl", args.impl]
                print(f"[run] {arch} {shape} {m}", flush=True)
                try:
                    proc = subprocess.run(cmd, capture_output=True, text=True,
                                          timeout=args.timeout)
                    if proc.returncode != 0:
                        failures += 1
                        out.write_text(json.dumps({
                            "status": "failed", "arch": arch, "shape": shape,
                            "mesh": m, "stderr": proc.stderr[-2000:]}))
                        print(f"  FAILED: {proc.stderr.strip().splitlines()[-1] if proc.stderr else '?'}")
                except subprocess.TimeoutExpired:
                    failures += 1
                    out.write_text(json.dumps({
                        "status": "timeout", "arch": arch, "shape": shape, "mesh": m}))
                    print("  TIMEOUT")
        print(f"done; {failures} failures")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    t0 = time.time()
    try:
        result = run_cell(args.arch, args.shape, args.mesh == "pod2", args.impl)
    except Exception:
        result = {"status": "error", "arch": args.arch, "shape": args.shape,
                  "mesh": args.mesh, "traceback": traceback.format_exc()[-4000:]}
    result["wall_s"] = round(time.time() - t0, 2)
    variant = os.environ.get("PAX_VARIANT", "")
    suffix = f"__{variant}" if variant else ""
    out = RESULTS_DIR / f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json"
    out.write_text(json.dumps(result, indent=2, default=str))
    if result["status"] == "ok":
        mm = result["memory"]
        rf = result["roofline"]
        print(f"== {args.arch} {args.shape} {args.mesh} [{result['mode']}] "
              f"lower {result['lower_s']}s compile {result['compile_s']}s")
        print(f"   memory/device: args {mm['argument_bytes']/2**30:.2f} GiB, "
              f"temp {mm['temp_bytes']/2**30:.2f} GiB, "
              f"peak~{mm['peak_estimate_bytes']/2**30:.2f} GiB")
        print(f"   roofline: compute {rf['compute_s']*1e3:.2f} ms, "
              f"memory {rf['memory_s']*1e3:.2f} ms, "
              f"collective {rf['collective_s']*1e3:.2f} ms -> {rf['bottleneck']}"
              f"  (useful-flops {rf['useful_flops_fraction']:.2f}, "
              f"MFU-bound {rf['mfu_bound']:.2f})")
    elif result["status"] == "skipped":
        print(f"== {args.arch} {args.shape} {args.mesh}: SKIPPED ({result['reason']})")
    else:
        print(result.get("traceback", result))
        sys.exit(1)


if __name__ == "__main__":
    main()
