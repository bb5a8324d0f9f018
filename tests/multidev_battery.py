"""Multi-device correctness battery, run in a subprocess with 8 fake CPU
devices (so the in-process test session keeps seeing 1 real device).

Run directly:  python tests/multidev_battery.py
Or via pytest: tests/test_collectives.py spawns it.

Sections:
  1. backend semantics equivalence (all backends vs numpy oracles)
  2. HLO identity: ABI(paxi) vs raw jax.lax  — the Table-1 zero-overhead claim
  3. bcast/sendrecv/scatter/alltoall/barrier correctness
  4. user ops + MINLOC across ranks (callback path)
  5. Mukautuva across ranks: alltoallw with per-peer dtypes + request map
  6. ring compression error bounds
  7. ZeRO-1 flat round trip across dp ranks (pooled nonblocking path)
  8. tiered negotiation: minimal backend emulation chains end-to-end
  9. persistent plans: plan-time hoisting == per-call semantics
 10. plan groups (Startall): group == per-plan zero1, dp=2 and dp=8
 11. hierarchical multi-axis alltoallv (world comm, 2x4 mesh)
 12. fused wire kernels inside real ring schedules (plan-time selection)
 13. fault tier: injected rank death on three dispatch paths
 14. elastic-dp: kill rank 5 at dp=8, shrink, bitwise resume at dp=4
 15. serving decode-tp plan group == pooled i* bcast (tp=4)
 16. serving fault supervisor: mid-decode kill at tp=4, heartbeat-observed
     death, shrink + token-identical replay (three dispatch paths)
 17. uneven-shard elastic recovery: dp=8 -> dp=7 (all survivors kept)
 18. transport integrity: corrupted zero1 collective detected -> retried ->
     bitwise resume; dropped decode-tp bcast -> timeout -> heartbeat
     confirm -> shrink -> token-identical replay (three dispatch paths)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.core as C
from repro.core.compat import make_mesh, shard_map
from repro.core import handles as H

mesh = make_mesh((2, 4), ("data", "model"))

XG = np.arange(64.0).reshape(8, 8) + 1.0  # rank-major chunks


def section(name):
    print(f"--- {name}")


# ---------------------------------------------------------------------------
section("1. backend semantics vs numpy oracles (every registered backend)")
exp_sum, exp_max, exp_min, exp_prod = XG.sum(0), XG.max(0), XG.min(0), XG.prod(0)
exp_scan = np.cumsum(XG, axis=0)                       # inclusive prefix, rank-major
exp_exscan = np.concatenate([XG[:1], exp_scan[:-1]])   # rank 0: input unchanged

# the equivalence battery runs over EVERY registered implementation — the
# spec-driven surface (including scan/exscan/alltoallv) must agree everywhere
for impl in sorted(C.available_backends()):
    abi = C.pax_init(mesh, impl=impl)
    world = C.PAX_COMM_WORLD
    dp = abi.comm_from_axes(("data",))
    mp = abi.comm_from_axes(("model",))

    def body(x):
        return (
            abi.allreduce(x, C.PAX_SUM, world),
            abi.allreduce(x, C.PAX_MAX, world),
            abi.allreduce(x, C.PAX_MIN, world),
            abi.allreduce(x, C.PAX_PROD, world),
            abi.allgather(x, dp),
            abi.reduce_scatter(x, C.PAX_SUM, world),
            abi.scan(x, C.PAX_SUM, world),
            abi.exscan(x, C.PAX_SUM, world),
            abi.alltoallv(x, (2, 2, 2, 2), (2, 2, 2, 2), mp),
            abi.alltoall(x.reshape(4, 2), mp, 0, 0).reshape(-1),
        )

    f = abi.shard_region(
        body, in_specs=P(("data", "model")),
        out_specs=(P(), P(), P(), P(), P("model"), P(("data", "model")),
                   P(("data", "model")), P(("data", "model")),
                   P(("data", "model")), P(("data", "model"))),
    )
    s, mx, mn, pr, ag, rs, sc, ex, a2av, a2a = jax.jit(f)(jnp.asarray(XG.reshape(-1)))
    tol = 0.03 if "int8" in impl else (0.01 if "bf16" in impl else 1e-5)
    np.testing.assert_allclose(np.asarray(s[:8]), exp_sum, rtol=tol)
    np.testing.assert_allclose(np.asarray(mx[:8]), exp_max)
    np.testing.assert_allclose(np.asarray(mn[:8]), exp_min)
    np.testing.assert_allclose(np.asarray(pr[:8]), exp_prod, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(rs), exp_sum, rtol=tol)
    np.testing.assert_allclose(
        np.asarray(ag[:16]), np.concatenate([XG[0], XG[4]])
    )  # model-col 0 gathers data-ranks {0,4}
    np.testing.assert_allclose(
        np.asarray(sc).reshape(8, 8), exp_scan, rtol=tol
    )  # inclusive prefix over linearized world rank
    np.testing.assert_allclose(
        np.asarray(ex).reshape(8, 8), exp_exscan, rtol=tol
    )  # exclusive prefix; rank 0 keeps its input (ABI convention)
    np.testing.assert_allclose(
        np.asarray(a2av), np.asarray(a2a), rtol=1e-6
    )  # uniform-count alltoallv == alltoall
    print(f"  {impl}: OK")

# ---------------------------------------------------------------------------
section("2. HLO identity: ABI(paxi) == raw jax.lax (Table 1, zero overhead)")
abi = C.pax_init(mesh, impl="paxi")


def step_abi(g):
    return abi.allreduce(g * 2.0, C.PAX_SUM, C.PAX_COMM_WORLD)


def step_raw(g):
    return jax.lax.psum(g * 2.0, ("data", "model"))


x = jnp.ones((8, 16))
spec = P(("data", "model"))
f_abi = jax.jit(shard_map(step_abi, mesh=mesh, in_specs=spec, out_specs=P()))
f_raw = jax.jit(shard_map(step_raw, mesh=mesh, in_specs=spec, out_specs=P()))


def norm_hlo(txt: str) -> str:
    """Keep only computation lines: strip op metadata and the source-location
    index tables (FileNames/FunctionNames/FileLocations/StackFrames)."""
    lines = []
    skipping = False
    for line in txt.splitlines():
        if line.strip() in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            skipping = True
            continue
        if skipping:
            if line.strip() == "":
                skipping = False
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        line = re.sub(r"HloModule \S+", "HloModule M", line)
        lines.append(line)
    return "\n".join(lines)


h_abi = norm_hlo(f_abi.lower(x).compile().as_text())
h_raw = norm_hlo(f_raw.lower(x).compile().as_text())
assert h_abi == h_raw, "ABI lowering differs from raw lax lowering!"
assert "all-reduce" in h_abi
print("  optimized HLO identical:", len(h_abi), "chars")

# ---------------------------------------------------------------------------
section("3. bcast / sendrecv / scatter / alltoall / barrier")
abi = C.pax_init(mesh, impl="paxi")
mp = abi.comm_from_axes(("model",))
world = C.PAX_COMM_WORLD


def body3(x):
    b = abi.bcast(x, root=3, comm=world)  # broadcast rank 3's chunk
    ring_perm = [(i, (i + 1) % 4) for i in range(4)]
    sr = abi.sendrecv(x, ring_perm, mp)
    a2a = abi.alltoall(x.reshape(4, 2), mp, 0, 0)
    abi.barrier(world)
    sc = abi.scatter(b, root=0, comm=world)  # split bcast chunk 8 ways
    return b, sr, a2a.reshape(-1), sc


f3 = abi.shard_region(
    body3, in_specs=P(("data", "model")),
    out_specs=(P(), P(("data", "model")), P(("data", "model")), P(("data", "model"))),
)
b, sr, a2a, sc = jax.jit(f3)(jnp.asarray(XG.reshape(-1)))
np.testing.assert_allclose(np.asarray(b[:8]), XG[3])  # everyone sees rank 3
# sendrecv ring over model: device (0,1) receives from (0,0)
np.testing.assert_allclose(np.asarray(sr[8:16]), XG[0])
# alltoall over model among ranks (0,0..3): device (0,0) collects block 0 of each
exp_a2a0 = np.concatenate([XG[m][0:2] for m in range(4)])
np.testing.assert_allclose(np.asarray(a2a[:8]), exp_a2a0)
# scatter of the bcast result: rank k gets elem k of XG[3]
np.testing.assert_allclose(np.asarray(sc), XG[3])
print("  OK")

# ---------------------------------------------------------------------------
section("4. user op + MINLOC across ranks")
abi = C.pax_init(mesh, impl="paxi")
opq = abi.op_create(lambda a, b: jnp.sqrt(a * a + b * b), name="l2")


def body4(x):
    q = abi.allreduce(x, opq, world)
    pairs = jnp.stack([x, jnp.full_like(x, C_rank())], axis=-1)
    ml = abi.allreduce(pairs, C.PAX_MINLOC, world)
    return q, ml


def C_rank():
    from repro.core.backends import _lax

    return _lax.rank(("data", "model")).astype(jnp.float32)


f4 = abi.shard_region(body4, in_specs=P(("data", "model")), out_specs=(P(), P()))
q, ml = jax.jit(f4)(jnp.asarray(XG.reshape(-1)))
np.testing.assert_allclose(np.asarray(q[:8]), np.sqrt((XG**2).sum(0)), rtol=1e-5)
np.testing.assert_allclose(np.asarray(ml[:8, 0]), XG.min(0))
np.testing.assert_allclose(np.asarray(ml[:8, 1]), XG.argmin(0))  # winning rank
print("  OK")

# ---------------------------------------------------------------------------
section("5. Mukautuva across ranks: alltoallw + trampoline")
abi = C.pax_init(mesh, impl="ompix")
mp = abi.comm_from_axes(("model",))
send_t = [C.PAX_FLOAT32] * 4
recv_t = [C.PAX_FLOAT64, C.PAX_FLOAT32, C.PAX_FLOAT64, C.PAX_FLOAT32]


def body5(x):
    blocks = x.reshape(4, 2)
    parts = abi.alltoallw(blocks, send_t, recv_t, mp)
    return tuple(p.astype(jnp.float32) for p in parts)


f5 = abi.shard_region(body5, in_specs=P(("data", "model")),
                      out_specs=tuple(P(("data", "model")) for _ in range(4)))
parts = jax.jit(f5)(jnp.asarray(XG.reshape(-1)))
np.testing.assert_allclose(np.asarray(parts[0])[:2], XG[0][0:2])
print("  alltoallw OK (per-peer dtype conversion via impl)")

opspy = abi.op_create(lambda a, b: a + b, name="sumspy")


def body5b(x):
    return abi.allreduce(x, opspy, world)


f5b = abi.shard_region(body5b, in_specs=P(("data", "model")), out_specs=P())
v = jax.jit(f5b)(jnp.asarray(XG.reshape(-1)))
np.testing.assert_allclose(np.asarray(v[:8]), exp_sum, rtol=1e-5)
print("  user-op through foreign backend OK")

# ---------------------------------------------------------------------------
section("6. ring compression error bounds")
gold = exp_sum
for impl, bound in (("ring-bf16", 0.01), ("ring-int8", 0.05)):
    abi = C.pax_init(mesh, impl=impl)
    f6 = abi.shard_region(
        lambda x: abi.allreduce(x, C.PAX_SUM, C.PAX_COMM_WORLD),
        in_specs=P(("data", "model")), out_specs=P(),
    )
    v = np.asarray(jax.jit(f6)(jnp.asarray(XG.reshape(-1)))[:8])
    rel = np.abs(v - gold) / np.abs(gold)
    assert rel.max() < bound, (impl, rel.max())
    print(f"  {impl}: max rel err {rel.max():.4f} < {bound}")

# scan/exscan on the compressed wire (the hierarchical multi-axis ring
# schedule; previously these fell back to the uncompressed generic fold).
# The error budget is bigger than rs/ag's: a contribution is re-quantized on
# every hop it travels and the row-total all-reduce adds its own hops.
for impl, bound in (("ring-bf16", 0.02), ("ring-int8", 0.05)):
    abi = C.pax_init(mesh, impl=impl)
    f6s = abi.shard_region(
        lambda x: (abi.scan(x, C.PAX_SUM, C.PAX_COMM_WORLD),
                   abi.exscan(x, C.PAX_SUM, C.PAX_COMM_WORLD)),
        in_specs=P(("data", "model")),
        out_specs=(P(("data", "model")), P(("data", "model"))),
    )
    sc6, ex6 = jax.jit(f6s)(jnp.asarray(XG.reshape(-1)))
    rel_sc = np.abs(np.asarray(sc6).reshape(8, 8) - exp_scan) / np.abs(exp_scan)
    rel_ex = np.abs(np.asarray(ex6).reshape(8, 8) - exp_exscan) / np.abs(exp_exscan)
    assert rel_sc.max() < bound, (impl, "scan", rel_sc.max())
    assert rel_ex.max() < bound, (impl, "exscan", rel_ex.max())
    print(f"  {impl}: scan/exscan max rel err "
          f"{max(rel_sc.max(), rel_ex.max()):.4f} < {bound}")

# ---------------------------------------------------------------------------
section("7. ZeRO-1 flat round trip across dp ranks (pooled nonblocking path)")
# dp=2 over the "data" axis: reduce-scatter of the dp-mean gradient, shard
# update f(g)=g*2, all-gather back must equal mean(g_dp) * 2 on every rank
from repro.runtime.dist import make_dist
from repro.train.grad_sync import zero1_step

dist = make_dist(mesh, impl="paxi")
assert dist.dp_size == 2, dist.dp_axes
NV = 16


def body7(v):
    params, ef = zero1_step(dist, v, lambda s: s * 2.0, buckets=2)
    assert ef is None
    return params


f7 = dist.abi.shard_region(body7, in_specs=P("data"), out_specs=P())
vin = np.arange(2 * NV, dtype=np.float32).reshape(-1)  # rank-major halves
out = np.asarray(jax.jit(f7)(jnp.asarray(vin))[:NV])
expect = (vin[:NV] + vin[NV:]) / 2.0 * 2.0
np.testing.assert_allclose(out, expect, rtol=1e-6)
assert dist.abi.outstanding_requests == 0
print("  zero1_step dp=2 buckets=2 OK (pool drained)")

# the train-loop flat layout: moments shard P(dp_axes), params replicated
from repro.optim import adamw as _adamw
from repro.train import train_loop as _tl

flat = _adamw.init_flat_global({"w": np.zeros(NV, np.float32)}, dist.dp_size,
                               buckets=2)
assert flat.m.shape[0] % (dist.dp_size * 2) == 0
print("  init_flat_global padding contract OK")

# body_zero1's alignment invariant at dp=2: the comm_rank_traced slice of a
# replicated flat vector, the P(dp_axes)-sharded view of the same vector,
# and the (transposed-split, bucketed) reduce-scatter shard must all be the
# SAME contiguous rank slice — moments would otherwise pair with the wrong
# gradient elements and training would silently diverge at dp>1
from repro.core.communicator import comm_rank_traced
from repro.train.grad_sync import reduce_scatter_grads

full = np.arange(NV, dtype=np.float32)       # NV=16, dp=2 -> shard 8
shard_len = NV // dist.dp_size


def body7b(m_shard, v_full):
    r = comm_rank_traced(dist.abi.comms.info(dist.dp_comm))
    p_slice = jax.lax.dynamic_slice_in_dim(v_full, r * shard_len, shard_len)
    # g_shard: dp-mean reduce-scatter of the replicated vector == rank slice
    g_shard, _ = reduce_scatter_grads(dist, v_full, buckets=2)
    return m_shard - p_slice, g_shard - p_slice


f7b = dist.abi.shard_region(
    body7b, in_specs=(P("data"), P()), out_specs=(P("data"), P("data")))
d_m, d_g = jax.jit(f7b)(jnp.asarray(full), jnp.asarray(full))
np.testing.assert_allclose(np.asarray(d_m), 0.0)  # sharded view == rank slice
np.testing.assert_allclose(np.asarray(d_g), 0.0)  # rs shard == rank slice
assert dist.abi.outstanding_requests == 0
print("  zero1 moment/param/grad shard alignment dp=2 OK")

# ---------------------------------------------------------------------------
section("8. tiered negotiation: minimal backend emulation chains end-to-end")
# The deliberately-partial backend (handle queries + sendrecv/reduce_scatter/
# allgather) must run the training round trip and the deepest recipe chains
# (scatter -> bcast -> allreduce -> rs+ag) purely through emulation.
dist_min = make_dist(mesh, impl="minimal")
caps = dist_min.abi.capabilities()
assert caps["allreduce"]["source"] == "emulated", caps["allreduce"]
assert caps["scatter"]["source"] == "emulated"
assert caps["scatter"]["deps"] == ("bcast", "comm_rank", "comm_size")
assert caps["reduce_scatter"]["source"] == "native"
assert not [n for n, i in caps.items() if i["source"] == "unavailable"]

out8 = np.asarray(jax.jit(dist_min.abi.shard_region(
    lambda v: zero1_step(dist_min, v, lambda s: s * 2.0, buckets=2)[0],
    in_specs=P("data"), out_specs=P()))(jnp.asarray(vin))[:NV])
np.testing.assert_allclose(out8, expect, rtol=1e-6)
assert dist_min.abi.outstanding_requests == 0
print("  zero1_step dp=2 on minimal backend OK (native rs/ag, pooled i*)")

abi_min = dist_min.abi
mp8 = abi_min.comm_from_axes(("model",))


def body8(x):
    # allreduce (emulated, depth 1), bcast (depth 2) and scatter (depth 3 —
    # the deepest chain), plus emulated alltoall/scan/barrier, all checked
    # against the native-oracle expectations from sections 1 and 3
    ar = abi_min.allreduce(x, C.PAX_SUM, world)
    b = abi_min.bcast(x, root=3, comm=world)
    sc8 = abi_min.scatter(b, root=0, comm=world)
    a2a = abi_min.alltoall(x.reshape(4, 2), mp8, 0, 0)
    s = abi_min.scan(x, C.PAX_SUM, world)
    abi_min.barrier(world)
    return ar, b, sc8, a2a.reshape(-1), s


f8 = abi_min.shard_region(
    body8, in_specs=P(("data", "model")),
    out_specs=(P(), P(), P(("data", "model")), P(("data", "model")),
               P(("data", "model"))),
)
ar8, b, sc8, a2a8, s8 = jax.jit(f8)(jnp.asarray(XG.reshape(-1)))
np.testing.assert_allclose(np.asarray(ar8[:8]), exp_sum, rtol=1e-5)
np.testing.assert_allclose(np.asarray(b[:8]), XG[3])
np.testing.assert_allclose(np.asarray(sc8), XG[3])
np.testing.assert_allclose(np.asarray(a2a8[:8]), exp_a2a0)
np.testing.assert_allclose(np.asarray(s8).reshape(8, 8), exp_scan, rtol=1e-5)
assert dist_min.abi.outstanding_requests == 0
print("  emulation chains (depth 1-3) match native oracles OK")

# ---------------------------------------------------------------------------
section("9. persistent plans: plan-time hoisting == per-call semantics (dp=2)")
# the zero1 round trip on persistent plans (the init_state wiring) must give
# byte-identical math to the pooled i* path of section 7, and the plans'
# restartable requests must flip inactive<->active across steps without
# touching the pool
from repro.train.grad_sync import build_zero1_plans

plans = build_zero1_plans(dist, NV, 2)
pool_before = len(dist.abi._req_pool)


def body9(v):
    params, ef = zero1_step(dist, v, lambda s: s * 2.0, buckets=2, plans=plans)
    assert ef is None
    return params


f9 = dist.abi.shard_region(body9, in_specs=P("data"), out_specs=P())
out9 = np.asarray(jax.jit(f9)(jnp.asarray(vin))[:NV])
np.testing.assert_allclose(out9, expect, rtol=1e-6)
# restart: a second trace re-drives the same plans (inactive -> active -> ...)
out9b = np.asarray(jax.jit(dist.abi.shard_region(
    body9, in_specs=P("data"), out_specs=P()))(jnp.asarray(vin))[:NV])
np.testing.assert_allclose(out9b, expect, rtol=1e-6)
assert dist.abi.outstanding_requests == 0
assert len(dist.abi._req_pool) == pool_before  # no slot churn across steps
print("  zero1 persistent-plan round trip dp=2 buckets=2 OK (slots reused)")

# emulated persistent plan with plan-time padding: 11 rows over an 8-rank
# world comm — the recipe plan precomputes pad=5 and the [:11] slice; result
# must match the blocking emulated allreduce exactly
abi_min9 = dist_min.abi
plan9 = abi_min9.allreduce_init(jnp.zeros(11, jnp.float32), C.PAX_SUM, world)
f9c = abi_min9.shard_region(
    lambda x: (abi_min9.wait(plan9.start(x)), abi_min9.allreduce(x, C.PAX_SUM, world)),
    in_specs=P(), out_specs=(P(), P()))
v_pers, v_block = jax.jit(f9c)(jnp.arange(11.0) + 1.0)
np.testing.assert_allclose(np.asarray(v_pers), np.asarray(v_block), rtol=1e-6)
np.testing.assert_allclose(np.asarray(v_pers), (np.arange(11.0) + 1.0) * 8)
caps9 = abi_min9.capabilities()
assert caps9["allreduce"]["plan"] == "recipe-plan"
print("  emulated persistent allreduce (plan-time pad/slice) dp=8 OK")

# error feedback through the zero1 wiring at dp=2: with bf16 compression the
# per-rank residual v - bf16(v) comes back from reduce_scatter_grads and,
# folded into the next step, makes the delivered sum unbiased:
#   g1 + g2 = bf16(v) + bf16(v + e1) = 2v - e2   (residuals never lost)
ef0 = jnp.zeros((2 * NV,), jnp.float32)  # per-rank full-length residuals
vfine = jnp.asarray(np.linspace(0.1, 1.7, NV, dtype=np.float32))  # inexact in bf16


def body9d(ef):
    g1, ef1 = reduce_scatter_grads(dist, vfine, compression="bf16", buckets=2,
                                   ef=ef)
    g2, ef2 = reduce_scatter_grads(dist, vfine, compression="bf16", buckets=2,
                                   ef=ef1)
    return g1, g2, ef1, ef2


f9d = dist.abi.shard_region(body9d, in_specs=P("data"),
                            out_specs=(P("data"),) * 4)
g1, g2, ef1, ef2 = (np.asarray(a) for a in jax.jit(f9d)(ef0))
v_np = np.asarray(vfine)
w1 = np.asarray(jnp.asarray(vfine).astype(jnp.bfloat16).astype(jnp.float32))
e1 = v_np - w1
assert np.abs(e1).max() > 0  # the bf16 residual is real for these values
np.testing.assert_allclose(ef1[:NV], e1, atol=0)   # rank 0's residual, exact
np.testing.assert_allclose(ef1[NV:], e1, atol=0)   # rank 1's (same grads)
np.testing.assert_allclose(g1, w1, rtol=0, atol=1e-7)  # dp-mean of wires
# the EF identity: two delivered steps sum to 2v minus only the *last*
# residual — the step-1 quantization error was recovered, not dropped
np.testing.assert_allclose(g1 + g2, 2 * v_np - ef2[:NV], rtol=0, atol=1e-6)
print(f"  zero1 bf16 error feedback dp=2 OK (residual max {np.abs(e1).max():.2e})")

# ---------------------------------------------------------------------------
section("10. plan groups (Startall): group == per-plan zero1, dp=2 and dp=8")
# The whole-group start/wait pair must deliver byte-identical math to the
# pooled per-bucket path, across a native backend (paxi: stacked-collective
# group hooks), the emulated-minimal backend (recipe stage fusion: all rs
# legs before any ag leg) and a Mukautuva-wrapped backend (generated group
# wrappers, conversion cached at group-build time) — at dp=2 (2x4 mesh) and
# dp=8 (8x1 mesh).
mesh8 = make_mesh((8, 1), ("data", "model"))
for impl10 in ("paxi", "minimal", "ompix"):
    for m10, dp10 in ((mesh, 2), (mesh8, 8)):
        d10 = make_dist(m10, impl=impl10)
        assert d10.dp_size == dp10
        plans10 = build_zero1_plans(d10, NV, 2)
        caps10 = d10.abi.capabilities()
        if impl10 == "minimal":
            assert caps10["allreduce"]["plan_group"] == "recipe-stage"
        else:
            assert caps10["allreduce"]["plan_group"] == "backend-hook"
        vin10 = np.arange(dp10 * NV, dtype=np.float32)
        exp10 = vin10.reshape(dp10, NV).mean(0) * 2.0

        def body10(v, _d=d10, _p=plans10):
            grouped = zero1_step(_d, v, lambda s: s * 2.0, buckets=2,
                                 plans=_p)[0]
            pooled = zero1_step(_d, v, lambda s: s * 2.0, buckets=2)[0]
            return grouped, pooled

        f10 = d10.abi.shard_region(body10, in_specs=P("data"),
                                   out_specs=(P(), P()))
        grouped, pooled = jax.jit(f10)(jnp.asarray(vin10))
        np.testing.assert_allclose(np.asarray(grouped[:NV]), exp10, rtol=1e-6,
                                   err_msg=f"{impl10} dp={dp10}")
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(pooled),
                                   rtol=0, atol=0,
                                   err_msg=f"{impl10} dp={dp10}")
        assert d10.abi.outstanding_requests == 0
        print(f"  {impl10} dp={dp10}: group == per-plan (bitwise) OK")

# the ring backend's fused compressed wire: the grouped rs/ag ride ONE ring
# schedule whose per-hop quantization covers all buckets; error stays within
# the section-6 budget and the uncompressed group is exact vs the oracle
for impl10, bound10 in (("ring", 0.0), ("ring-bf16", 0.01)):
    d10 = make_dist(mesh, impl=impl10)
    plans10 = build_zero1_plans(d10, NV, 2)
    vin10 = np.arange(2 * NV, dtype=np.float32) + 1.0
    exp10 = vin10.reshape(2, NV).mean(0) * 2.0
    f10 = d10.abi.shard_region(
        lambda v, _d=d10, _p=plans10: zero1_step(
            _d, v, lambda s: s * 2.0, buckets=2, plans=_p)[0],
        in_specs=P("data"), out_specs=P())
    out10 = np.asarray(jax.jit(f10)(jnp.asarray(vin10))[:NV])
    if bound10 == 0.0:
        np.testing.assert_allclose(out10, exp10, rtol=1e-6, err_msg=impl10)
    else:
        rel10 = np.abs(out10 - exp10) / np.maximum(np.abs(exp10), 1e-6)
        assert rel10.max() < bound10, (impl10, rel10.max())
    assert d10.abi.outstanding_requests == 0
    print(f"  {impl10}: fused-wire grouped zero1 OK")

# ---------------------------------------------------------------------------
section("11. hierarchical multi-axis alltoallv (world comm, 2x4 mesh)")
# alltoallv over the 8-rank world communicator decomposes axis by axis (the
# ring_scan_sum_multi pattern): with c=1 and rank r holding XG[r], peer j
# receives element r — the result is the global transpose.  c=2 checks the
# block layout too.  Oracles are pure numpy; every backend must agree
# (paxi/ring lower natively, minimal emulates over allgather, ompix crosses
# Mukautuva).
for impl11 in ("paxi", "ring", "minimal", "ompix"):
    abi11 = C.pax_init(mesh, impl=impl11)
    f11 = abi11.shard_region(
        lambda x: abi11.alltoallv(x, (1,) * 8, (1,) * 8, world),
        in_specs=P(("data", "model")), out_specs=P(("data", "model")))
    out11 = np.asarray(jax.jit(f11)(jnp.asarray(XG.reshape(-1)))).reshape(8, 8)
    np.testing.assert_allclose(out11, XG.T, err_msg=impl11)
    X2 = np.arange(128.0).reshape(8, 16)
    f11b = abi11.shard_region(
        lambda x: abi11.alltoallv(x, (2,) * 8, (2,) * 8, world),
        in_specs=P(("data", "model")), out_specs=P(("data", "model")))
    out11b = np.asarray(jax.jit(f11b)(jnp.asarray(X2.reshape(-1)))).reshape(8, 16)
    exp11b = np.stack([X2[:, 2 * r:2 * r + 2].reshape(-1) for r in range(8)])
    np.testing.assert_allclose(out11b, exp11b, err_msg=impl11)
    print(f"  {impl11}: multi-axis alltoallv == transpose oracle OK")

# ---------------------------------------------------------------------------
section("12. fused wire kernels inside real ring schedules (plan-time selection)")
# Sections 6/10 exercised the compressed ring at shapes the Pallas hop
# kernels decline (per-hop chunks not WIRE_BLOCK-divisible) — proving the
# lax fallback.  Here the shapes are kernel-eligible: at dp=2 a 1024-element
# zero1 with 2 buckets gives 256-element ring chunks (fused hop kernels
# live), at dp=8 the 64-element chunks fall back to lax while the fused
# flatten/bucket pack kernels stay engaged — both legs of the plan-time
# selection contract in one section.
from repro.kernels.ring_wire.kernel import WIRE_BLOCK as _WB

NV12 = 8 * _WB  # 1024

# capability tags: the compressed ring advertises its wire pipeline
for impl12, want12 in (("ring-int8", "pallas"), ("ring-bf16", "pallas"),
                       ("ring", "lax"), ("paxi", None)):
    caps12 = C.pax_init(mesh, impl=impl12).capabilities()
    got12 = caps12["reduce_scatter"].get("wire_kernel")
    assert got12 == want12, (impl12, got12)
print("  capabilities()[reduce_scatter][wire_kernel] tags OK")

# grouped zero1 over the compressed ring at a kernel-eligible layout
for impl12, bound12 in (("ring-bf16", 0.01), ("ring-int8", 0.05)):
    d12 = make_dist(mesh, impl=impl12)
    plans12 = build_zero1_plans(d12, NV12, 2)
    assert plans12.wire_kernel == "pallas"  # fused pack/unpack attached
    vin12 = np.linspace(0.1, 33.0, 2 * NV12, dtype=np.float32)
    exp12 = vin12.reshape(2, NV12).mean(0) * 2.0
    f12 = d12.abi.shard_region(
        lambda v, _d=d12, _p=plans12: zero1_step(
            _d, v, lambda s: s * 2.0, buckets=2, plans=_p)[0],
        in_specs=P("data"), out_specs=P())
    out12 = np.asarray(jax.jit(f12)(jnp.asarray(vin12))[:NV12])
    rel12 = np.abs(out12 - exp12) / np.maximum(np.abs(exp12), 1e-6)
    assert rel12.max() < bound12, (impl12, rel12.max())
    assert d12.abi.outstanding_requests == 0
    print(f"  {impl12}: fused-hop grouped zero1 (256-elem chunks) "
          f"max rel err {rel12.max():.4f} < {bound12}")

# the EF identity of section 9d re-proven on the FUSED pack path (the
# pack_parts_ef kernel folds ef + casts + gathers in one pass) at dp=2 and
# dp=8 — residual semantics must be bit-identical to the lax pipeline
vfine12 = jnp.asarray(np.linspace(0.1, 1.7, NV12, dtype=np.float32))
for m12, dp12 in ((mesh, 2), (mesh8, 8)):
    d12 = make_dist(m12, impl="paxi")
    plans12 = build_zero1_plans(d12, NV12, 2, compression="bf16")
    assert plans12.wire_kernel == "pallas" and plans12.pack is not None

    def body12(ef, _d=d12, _p=plans12):
        g1, ef1 = reduce_scatter_grads(_d, vfine12, compression="bf16",
                                       buckets=2, ef=ef, plans=_p)
        g2, ef2 = reduce_scatter_grads(_d, vfine12, compression="bf16",
                                       buckets=2, ef=ef1, plans=_p)
        return g1, g2, ef1, ef2

    f12b = d12.abi.shard_region(body12, in_specs=P("data"),
                                out_specs=(P("data"),) * 4)
    g1, g2, ef1, ef2 = (np.asarray(a)
                        for a in jax.jit(f12b)(jnp.zeros((dp12 * NV12,),
                                                         jnp.float32)))
    v_np = np.asarray(vfine12)
    w1 = np.asarray(vfine12.astype(jnp.bfloat16).astype(jnp.float32))
    e1 = v_np - w1
    assert np.abs(e1).max() > 0
    np.testing.assert_allclose(ef1[:NV12], e1, atol=0)  # fused residual exact
    np.testing.assert_allclose(g1, w1, rtol=0, atol=1e-7)
    np.testing.assert_allclose(g1 + g2, 2 * v_np - ef2[:NV12],
                               rtol=0, atol=1e-6)
    assert d12.abi.outstanding_requests == 0
    print(f"  fused-pack bf16 error feedback dp={dp12} OK "
          f"(residual max {np.abs(e1).max():.2e})")

# emulated allreduce over the compressed ring at a non-aligned length: the
# recipe plan pads 1000 -> 1024 (S * wire_block) at plan time, so the rs
# leg's 128-element chunks stay kernel-eligible (per-block scales), while
# the blocking call pads only to S (125-element chunks -> lax global-scale
# fallback).  The two are *different* valid int8 approximations — each must
# meet the section-6 budget against the exact oracle, and the kernel path
# (finer scale granularity) must not be the worse of the two.
abi12 = C.pax_init(mesh, impl="ring-int8")
assert abi12.backend.wire_pad_multiple() == _WB
plan12 = abi12.allreduce_init(jnp.zeros(1000, jnp.float32), C.PAX_SUM, world)
f12c = abi12.shard_region(
    lambda x: (abi12.wait(plan12.start(x)),
               abi12.allreduce(x, C.PAX_SUM, world)),
    in_specs=P(), out_specs=(P(), P()))
x12 = jnp.asarray(np.linspace(0.5, 40.0, 1000, dtype=np.float32))
v_pers12, v_block12 = jax.jit(f12c)(x12)
gold12 = 8.0 * np.asarray(x12)
rel_pers = np.abs(np.asarray(v_pers12) - gold12) / gold12
rel_block = np.abs(np.asarray(v_block12) - gold12) / gold12
assert rel_pers.max() < 0.05, rel_pers.max()
# the global-scale fallback is coarser on this 80x-dynamic-range input;
# it gets a proportionally looser budget (the kernel path is the one the
# section-6 0.05 budget must hold for)
assert rel_block.max() < 0.06, rel_block.max()
assert rel_pers.max() <= rel_block.max() + 1e-6, (rel_pers.max(),
                                                  rel_block.max())
print(f"  ring-int8 persistent allreduce n=1000 (block-padded recipe) "
      f"max rel err {rel_pers.max():.4f} (blocking lax {rel_block.max():.4f})"
      " OK")

# ---------------------------------------------------------------------------
section("13. fault tier: injected rank death on three dispatch paths (dp=8)")
# The same ULFM walk — kill -> PROC_FAILED, revoke -> REVOKED exactly,
# ack/agree, shrink 8 -> 7 — through three different dispatch stories:
# paxi (native fault hooks, tripwired optional entries), minimal (recipe
# emulation over the shared kernels) and ompix (failure injected as a
# foreign rc, translated across Mukautuva).
from repro.core.backends.faulty import (FaultSchedule, FaultyBackend,
                                        FaultyLib, fault_schedule_of)
from repro.core.backends.ompix import OmpixLib
from repro.core.mukautuva import MukBackend
from repro.core.errors import (PAX_ERR_PROC_FAILED, PAX_ERR_REVOKED, PaxError)


def make_faulty(impl, m, sched):
    if impl == "ompix":
        return MukBackend(FaultyLib(OmpixLib(m), sched), m)
    return FaultyBackend(C.get_backend(impl, m), sched)


for impl13 in ("paxi", "minimal", "ompix"):
    sched13 = FaultSchedule()
    abi13 = C.pax_init(mesh8, impl=make_faulty(impl13, mesh8, sched13))
    dp13 = abi13.comm_from_axes(("data",), "dp")
    want13 = "native" if impl13 == "paxi" else "emulated"
    caps13 = abi13.capabilities()
    for e13 in ("comm_revoke", "comm_failure_ack", "comm_get_failed",
                "comm_agree", "comm_shrink"):
        assert caps13[e13]["tier"] == "fault", (impl13, e13)
        assert caps13[e13]["source"] == want13, (impl13, e13, caps13[e13])

    def run13(_abi=None, _dp=None):
        _abi, _dp = _abi or abi13, _dp or dp13
        f = _abi.shard_region(lambda x: _abi.allreduce(x, C.PAX_SUM, _dp),
                              in_specs=P("data"), out_specs=P())
        return np.asarray(jax.jit(f)(jnp.ones(8, np.float32)))

    assert run13()[0] == 8.0  # pre-fault: clean dispatch
    sched13.arm(5, after=0)
    try:
        run13()
        raise AssertionError(f"{impl13}: injected death did not surface")
    except PaxError as e13x:
        assert e13x.code == PAX_ERR_PROC_FAILED, (impl13, e13x.code)
    # the detector reports the corpse; agree refuses before acknowledgement
    assert abi13.comm_get_failed(dp13) == (5,), impl13
    try:
        abi13.comm_agree(1, dp13)
        raise AssertionError(f"{impl13}: agree accepted unacked failure")
    except PaxError as e13x:
        assert e13x.code == PAX_ERR_PROC_FAILED
    abi13.comm_revoke(dp13)
    try:
        run13()
        raise AssertionError(f"{impl13}: revoked comm still dispatches")
    except PaxError as e13x:  # REVOKED outranks PROC_FAILED (ULFM)
        assert e13x.code == PAX_ERR_REVOKED, (impl13, e13x.code)
    # fault entries keep working on the revoked comm; shrink recovers
    abi13.comm_failure_ack(dp13)
    assert abi13.comm_agree(1, dp13) == 1
    surv13 = abi13.comm_shrink(dp13)
    assert abi13.comms.info(surv13).excludes == (5,)
    assert abi13.comm_size(surv13) == 7
    # on the survivor comm the corpse is a non-member, not a failure
    assert abi13.comm_get_failed(surv13) == ()
    assert abi13.comm_agree(1, surv13) == 1
    print(f"  {impl13}: kill->PROC_FAILED, revoke->REVOKED, shrink 8->7 OK")

# CI chaos leg: when PAX_FAULT_SCHEDULE is set, the registry's faulty:
# prefix must arm from the environment and the schedule must fire at the
# configured call count — the deterministic chaos contract.
env13 = os.environ.get("PAX_FAULT_SCHEDULE")
se13 = None
if env13:
    abi13e = C.pax_init(mesh8, impl="faulty:paxi")
    se13 = fault_schedule_of(abi13e.backend)
    assert se13 is not None and se13.armed, env13
if se13 is not None and se13.mode != "die":
    # transport schedules (corrupt/drop/delay) exercise section 18's env
    # leg instead — they never set ``dead``, so the death walk below would
    # be vacuous
    print(f"  env chaos schedule {env13!r}: transport mode, see section 18")
elif se13 is not None:
    dpe13 = abi13e.comm_from_axes(("data",), "dp")
    for _ in range(se13.at_call + 1):  # drive the counter to the kill point
        se13.on_call()
    assert se13.dead
    try:
        run13(abi13e, dpe13)
        raise AssertionError("env-armed schedule did not fire")
    except PaxError as e13x:
        assert e13x.code == PAX_ERR_PROC_FAILED
    abi13e.comm_revoke(dpe13)
    abi13e.comm_failure_ack(dpe13)
    surv13e = abi13e.comm_shrink(dpe13)
    lost13 = 1 if 0 <= se13.kill_rank < 8 else 0
    assert abi13e.comm_size(surv13e) == 8 - lost13
    print(f"  env chaos schedule {env13!r}: fired and recovered OK")

# ---------------------------------------------------------------------------
section("14. elastic-dp: kill rank 5 at dp=8, shrink, bitwise resume at dp=4")
# The end-to-end recovery contract: supervised training at dp=8 loses rank 5
# mid-run; the fault-tier walk shrinks the world, the policy rebuilds a
# dp=4 mesh over the survivors (power-of-two trim of the 7), the checkpoint
# reshards onto it, and the resumed trajectory is BITWISE identical to an
# uninterrupted dp=4 oracle restored from the same checkpoint.  Replay is
# bounded by the checkpoint cadence (the recovery_steps_overhead gate).
import shutil
import tempfile

import repro.configs as cfgs
from repro.checkpoint.checkpointer import Checkpointer
from repro.models import build_model, make_batch
from repro.optim.adamw import AdamWConfig
from repro.runtime.dist import survivor_mesh
from repro.runtime.fault import run_supervised
from repro.train import train_loop

cfg14 = cfgs.smoke_config("qwen2-0.5b")
api14 = build_model(cfg14)
key14 = jax.random.PRNGKey(0)
opt14 = AdamWConfig(lr=5e-3)
TOTAL14, EVERY14, KILL_AT14, KILL_RANK14 = 8, 4, 6, 5

n14 = sum(int(x.size) for x in jax.tree.leaves(api14.init(key14)))
# flat zero1 layout identical at dp=8 and dp=4
assert _adamw.zero1_padded_size(n14, 8) == _adamw.zero1_padded_size(n14, 4)


def batch_at14(step):
    return make_batch(jax.random.PRNGKey(1000 + step), cfg14, 8, 16)


mesh4 = jax.sharding.Mesh(
    np.array(jax.devices()[:4], dtype=object).reshape(4, 1),
    ("data", "model"))
# the policy's survivor trim must land on exactly this mesh
smesh14 = survivor_mesh(mesh8, (KILL_RANK14,))
assert tuple(smesh14.devices.flat[:4]) == tuple(mesh4.devices.flat)

for impl14 in ("paxi", "minimal", "ompix"):
    sched14 = FaultSchedule()
    dist8 = make_dist(mesh8, impl=make_faulty(impl14, mesh8, sched14))
    assert dist8.dp_size == 8
    state0 = train_loop.init_state(api14, key14, dist8)
    step8 = train_loop.with_failure_probe(
        dist8, jax.jit(train_loop.make_train_step(api14, dist8, opt14)))
    policy14 = train_loop.elastic_recovery_policy(
        api14, opt14, dist8, key14, impl=impl14)
    killed14 = []

    def get_batch14(i, _s=sched14, _k=killed14):
        if i == KILL_AT14 and not _k:
            _k.append(i)
            _s.kill_rank = KILL_RANK14
            _s.dead = True  # the detector now reports rank 5 dead
        return batch_at14(i)

    ckdir14 = tempfile.mkdtemp(prefix=f"elastic_{impl14}_")
    ck14 = Checkpointer(ckdir14, keep=5)
    report14 = run_supervised(
        step8, state0, get_batch14, checkpointer=ck14,
        total_steps=TOTAL14, checkpoint_every=EVERY14, max_restarts=2,
        recover=policy14)
    assert report14.restarts == 1, impl14
    assert report14.steps_completed == TOTAL14
    assert len(report14.losses) == TOTAL14  # one loss per step, replay-clean
    assert policy14.dist.dp_size == 4      # 7 survivors -> power-of-two trim
    assert policy14.dist is not dist8

    # the oracle: an uninterrupted dp=4 run restored from the SAME step-4
    # checkpoint, on the same survivor devices, with the plain backend
    dist4 = make_dist(mesh4, impl=impl14)
    like4 = train_loop.init_state(api14, key14, dist4)
    specs4 = train_loop.state_specs(api14, "abi", dp_axes=dist4.dp_axes)
    state4, step4 = ck14.restore(like4, step=EVERY14, mesh=mesh4, specs=specs4)
    assert step4 == EVERY14  # replayed steps <= checkpoint_every
    jstep4 = jax.jit(train_loop.make_train_step(api14, dist4, opt14))
    for s14 in range(EVERY14, TOTAL14):
        state4, _m14 = jstep4(state4, batch_at14(s14))
    v_leaves = jax.tree.leaves(report14.final_state)
    o_leaves = jax.tree.leaves(state4)
    assert len(v_leaves) == len(o_leaves)
    for a14, b14 in zip(v_leaves, o_leaves):
        np.testing.assert_array_equal(np.asarray(a14), np.asarray(b14))
    shutil.rmtree(ckdir14, ignore_errors=True)
    print(f"  {impl14}: death at step {KILL_AT14} -> dp=4 resume "
          "bitwise == oracle OK")

# ---------------------------------------------------------------------------
section("15. serving decode-tp plan group == pooled i* bcast (tp=4)")
# The serve engine's per-token control-plane sync (sampled tokens + active
# mask broadcast from tp root 0) rides ONE persistent plan group built at
# engine init.  Across backends, the group start/wait must be bitwise equal
# to the pooled nonblocking ibcast/waitall reference on genuinely different
# per-rank data (tp_comm spans "model", size 4), and a counting tool must
# see exactly one "decode-tp" call per step and none of the pooled entries.
from repro.serve.engine import DecodeSync

MB15 = 8
tok15 = jnp.arange(4 * MB15, dtype=jnp.int32) * 3 + 1   # rank-major blocks
act15 = (jnp.arange(4 * MB15, dtype=jnp.int32) % 2).astype(jnp.int32)
exp_tok15 = np.tile(np.asarray(tok15[:MB15]), 4)        # root 0's block
exp_act15 = np.tile(np.asarray(act15[:MB15]), 4)
for impl15 in ("paxi", "minimal", "ompix"):
    if impl15 not in C.available_backends():
        continue
    dist15 = make_dist(mesh, impl=impl15)
    abi15 = dist15.abi
    cc15 = C.CallCounter()
    abi15.attach_tool(cc15)
    ds15 = DecodeSync(abi15, dist15.tp_comm, MB15, mesh)
    spec15 = (P("model"), P("model"))

    def grp15(t, a, _ds=ds15, _abi=abi15):
        outs = _abi.wait(_ds.group.start([t, a]))
        return outs[0], outs[1]

    def pool15(t, a, _ds=ds15, _abi=abi15):
        outs = _abi.waitall([_abi.ibcast(t, 0, _ds.comm),
                             _abi.ibcast(a, 0, _ds.comm)])
        return outs[0], outs[1]

    for _rep15 in range(3):   # restartable: same group slot every step
        gt15, ga15 = shard_map(grp15, mesh=mesh, in_specs=spec15,
                               out_specs=spec15)(tok15, act15)
        pt15, pa15 = shard_map(pool15, mesh=mesh, in_specs=spec15,
                               out_specs=spec15)(tok15, act15)
        np.testing.assert_array_equal(np.asarray(gt15), np.asarray(pt15))
        np.testing.assert_array_equal(np.asarray(ga15), np.asarray(pa15))
    np.testing.assert_array_equal(np.asarray(gt15), exp_tok15)
    np.testing.assert_array_equal(np.asarray(ga15), exp_act15)
    assert cc15.counts[DecodeSync.NAME] == 3, cc15.counts
    assert cc15.counts["bcast"] == 6, cc15.counts  # pooled reference only
    ds15.free()
    print(f"  {impl15}: decode-tp group == pooled (bitwise), "
          "1 group call/step OK")

# ---------------------------------------------------------------------------
section("16. serving fault supervisor: mid-decode kill at tp=4, heartbeat-"
        "observed death, shrink + token-identical replay")
# The PR-9 acceptance scenario.  A supervised serving engine loses a tp
# rank mid-decode with THREE requests in flight.  The backend does NOT
# declare the death (declare_failures=False — the silent-killer mode):
# only the HeartbeatMonitor's missed-beat state machine can name the
# corpse, via the heartbeat_silent transport hook.  The supervisor walks
# revoke -> ack -> get_failed -> agree -> shrink on the tp comm, rebuilds
# DecodeSync on the shrunk survivor comm, and replays the in-flight
# requests from their prompts.  Because sampling keys are
# fold_in(fold_in(key, rid), len(out_tokens)), the replayed streams must
# be BITWISE identical to an unfailed oracle — on all three dispatch
# paths (paxi native, minimal emulation, ompix across Mukautuva).
from repro.runtime.liveness import HeartbeatMonitor
from repro.serve.engine import Request, ServeEngine
from repro.serve.supervisor import ServeSupervisor

params16 = api14.init(jax.random.PRNGKey(0))


def mk_reqs16():
    # request 1 samples at temperature 0.8: replay identity must hold for
    # seeded sampling, not just greedy argmax
    return [Request(i, np.arange(1, 6 + i, dtype=np.int32),
                    max_new_tokens=16, temperature=0.8 if i == 1 else 0.0)
            for i in range(3)]


def make_faulty16(impl, m, sched):
    if impl == "ompix":
        return MukBackend(FaultyLib(OmpixLib(m), sched,
                                    declare_failures=False), m)
    return FaultyBackend(C.get_backend(impl, m), sched,
                         declare_failures=False)


# ONE engine: the jitted prefill/decode functions compile once and every
# leg (oracle + three impls) reuses them — only the DecodeSync, monitor
# and supervisor are per-impl.
eng16 = ServeEngine(api14, params16, max_batch=3, max_seq=64, block_size=4,
                    prefill_chunk=4, seed=0)
oreqs16 = mk_reqs16()
eng16.run(oreqs16)
want16 = [r.out_tokens for r in oreqs16]

for impl16 in ("paxi", "minimal", "ompix"):
    sched16 = FaultSchedule()
    abi16 = C.pax_init(mesh, impl=make_faulty16(impl16, mesh, sched16))
    tp16 = abi16.comm_from_axes(("model",), "tp")
    eng16.decode_sync = DecodeSync(abi16, tp16, 3, mesh)
    mon16 = HeartbeatMonitor(abi16, tp16, mesh, miss_threshold=2,
                             suspicion_ticks=1).install()
    sup16 = ServeSupervisor(eng16, monitor=mon16, heartbeat_every=1)
    for r16 in mk_reqs16():
        eng16.submit(r16)
    reqs16 = list(eng16.scheduler.waiting)
    # step until every slot is decoding — max_new_tokens=16 keeps the
    # earliest request alive long past the last one's prefill runway, so
    # the all-decoding window is guaranteed to exist
    while not all(s16 is not None and s16.state == "decode"
                  for s16 in eng16.scheduler.slots):
        sup16.step()
    mid16 = [len(r16.out_tokens) for r16 in reqs16]
    assert all(m16 > 0 for m16 in mid16), mid16   # genuinely mid-decode
    sched16.arm(2, after=0)                        # rank 2 dies silently
    sup16.drain()
    got16 = [r16.out_tokens for r16 in reqs16]
    assert got16 == want16, (impl16, got16, want16)
    assert sup16.report.failures == 1, sup16.report
    assert sup16.report.tokens_replayed == sum(mid16), (
        sup16.report.tokens_replayed, mid16)
    assert abi16.comms.info(eng16.decode_sync.comm).excludes == (2,)
    assert 2 in mon16.confirmed                    # observed, not declared
    sup16.report.assert_consistent()
    mon16.uninstall()
    eng16.decode_sync.free()
    eng16.decode_sync = None
    print(f"  {impl16}: mid-decode kill (in-flight {mid16}) -> shrink, "
          f"replay {sup16.report.tokens_replayed} tokens, "
          "streams bitwise == oracle OK")

# CI chaos-serve leg: with PAX_FAULT_SCHEDULE armed, the registry's
# faulty: prefix feeds the serving supervisor too.  The scheduled rank is
# killed up front (counter driven to the kill point, as in section 13);
# if it is a member of the tp comm the supervisor must recover before a
# single token is lost, and if it is NOT a member (the training chaos
# leg's rank=5 vs tp full size 4) the run must complete unfailed — the
# detectors filter by membership.
env16 = os.environ.get("PAX_FAULT_SCHEDULE")
se16 = None
if env16:
    abi16e = C.pax_init(mesh, impl="faulty:paxi")
    se16 = fault_schedule_of(abi16e.backend)
    assert se16 is not None and se16.armed, env16
if se16 is not None and se16.mode != "die":
    print(f"  env chaos schedule {env16!r}: transport mode, see section 18")
elif se16 is not None:
    tp16e = abi16e.comm_from_axes(("model",), "tp")
    eng16.decode_sync = DecodeSync(abi16e, tp16e, 3, mesh)
    mon16e = HeartbeatMonitor(abi16e, tp16e, mesh, miss_threshold=2,
                              suspicion_ticks=1).install()
    sup16e = ServeSupervisor(eng16, monitor=mon16e, heartbeat_every=1)
    for _ in range(se16.at_call + 1):   # drive the counter to the kill
        se16.on_call()
    assert se16.dead
    member16 = 0 <= se16.kill_rank < abi16e.comms.info(tp16e).full_size
    oreqs16e = mk_reqs16()
    for r16 in oreqs16e:
        eng16.submit(r16)
    sup16e.drain()
    assert [r16.out_tokens for r16 in oreqs16e] == want16
    if member16:
        assert sup16e.report.failures == 1, sup16e.report
        assert abi16e.comms.info(eng16.decode_sync.comm).excludes == (
            se16.kill_rank,)
    else:
        assert sup16e.report.failures == 0, sup16e.report
    sup16e.report.assert_consistent()
    mon16e.uninstall()
    eng16.decode_sync.free()
    eng16.decode_sync = None
    print(f"  env chaos schedule {env16!r}: serve leg "
          f"{'recovered' if member16 else 'unfailed (non-member corpse)'}"
          " OK")

# ---------------------------------------------------------------------------
section("17. uneven-shard elastic recovery: dp=8 -> dp=7, all survivors kept")
# The power-of-two trim in section 14 throws away three healthy ranks when
# one dies.  elastic_recovery_policy(uneven_shards=True) keeps all seven:
# the global batch is rebalanced per step (host-side trim to a dp
# multiple, deterministically the tail), and the per-leaf DDP optimizer
# layout replaces the zero1 flat layout (which pads per-dp-extent and
# cannot restore an old checkpoint shape at a new dp).  The resumed
# trajectory must be bitwise identical to an uninterrupted dp=7 oracle
# restored from the same checkpoint and fed the same rebalanced batches.
import dataclasses

cfg17 = dataclasses.replace(
    cfg14, parallelism=dataclasses.replace(cfg14.parallelism, zero1=False))
api17 = build_model(cfg17)
sched17 = FaultSchedule()
dist17 = make_dist(mesh8, impl=make_faulty("paxi", mesh8, sched17))
state17 = train_loop.init_state(api17, key14, dist17)
step17 = train_loop.with_failure_probe(
    dist17, jax.jit(train_loop.make_train_step(api17, dist17, opt14)))
policy17 = train_loop.elastic_recovery_policy(
    api17, opt14, dist17, key14, impl="paxi", uneven_shards=True)
killed17 = []


def batch_at17(step):
    return make_batch(jax.random.PRNGKey(1000 + step), cfg17, 8, 16)


def get_batch17(i):
    if i == KILL_AT14 and not killed17:
        killed17.append(i)
        sched17.kill_rank = KILL_RANK14
        sched17.dead = True
    return batch_at17(i)


ckdir17 = tempfile.mkdtemp(prefix="uneven_")
ck17 = Checkpointer(ckdir17, keep=5)
report17 = run_supervised(
    step17, state17, get_batch17, checkpointer=ck17,
    total_steps=TOTAL14, checkpoint_every=EVERY14, max_restarts=2,
    recover=policy17)
assert report17.restarts == 1
assert report17.steps_completed == TOTAL14
assert policy17.dist.dp_size == 7      # every survivor kept, no trim

# oracle: uninterrupted dp=7 run restored from the SAME step-4 checkpoint
# on the survivor mesh, fed the SAME tail-trimmed batches
mesh7 = survivor_mesh(mesh8, (KILL_RANK14,))
assert mesh7.shape["data"] == 7
dist7 = make_dist(mesh7, impl="paxi")
like7 = train_loop.init_state(api17, key14, dist7)
specs7 = train_loop.state_specs(api17, "abi")   # per-leaf DDP layout
state7, step7 = ck17.restore(like7, step=EVERY14, mesh=mesh7, specs=specs7)
assert step7 == EVERY14
jstep7 = jax.jit(train_loop.make_train_step(api17, dist7, opt14))
for s17 in range(EVERY14, TOTAL14):
    state7, _m17 = jstep7(state7, train_loop.rebalance_batch(
        batch_at17(s17), 7))
v17 = jax.tree.leaves(report17.final_state)
o17 = jax.tree.leaves(state7)
assert len(v17) == len(o17)
for a17, b17 in zip(v17, o17):
    np.testing.assert_array_equal(np.asarray(a17), np.asarray(b17))
shutil.rmtree(ckdir17, ignore_errors=True)
print(f"  paxi: death at step {KILL_AT14} -> dp=7 uneven resume "
      "bitwise == oracle OK")

# ---------------------------------------------------------------------------
section("18. transport integrity: corrupted zero1 collective + dropped "
        "decode-tp bcast (three dispatch paths)")
# The PR-10 acceptance scenario, both halves of the escalation funnel.
#
# Training half: one zero1 collective is corrupted mid-run at dp=8 with
# integrity mode ON.  The checksummed plan-group closure detects the
# disagreement in-trace and folds the canonical poison into the payload;
# ``verify_clean`` (the RetryPolicy's verify hook) raises
# PAX_ERR_DATA_CORRUPTION at materialization, the policy re-runs the step
# (corruption is one-shot, so the retry is clean) and the finished
# trajectory must be BITWISE identical to an unfailed oracle on the same
# backend.  The injection fires at trace time, so arming re-jits the step
# through a fresh callable (jax caches traces per function identity).
#
# Serving half: one decode-tp broadcast is dropped mid-decode at tp=4 —
# a real hang, surfaced only by the DecodeSync wait timeout.  The
# supervisor retries in place (``transport_retries``), the drop is sticky,
# and the exhausted retry escalates into the PR-9 walk: heartbeat confirm
# (a dropping link stops answering heartbeats) -> revoke -> shrink ->
# rebuild -> replay, streams bitwise equal to the unfailed oracle.
import time as _time

from repro.core.errors import (PAX_ERR_DATA_CORRUPTION, PAX_ERR_REQUEST,
                               PAX_ERR_TIMEOUT)
from repro.runtime.fault import RetryPolicy

for impl18 in ("paxi", "minimal", "ompix"):
    sched18 = FaultSchedule()
    dist18 = make_dist(mesh8, impl=make_faulty(impl18, mesh8, sched18),
                       integrity=True)
    assert dist18.abi.integrity
    state18 = train_loop.init_state(api14, key14, dist18)
    raw18 = train_loop.make_train_step(api14, dist18, opt14)

    def fresh18(_raw=raw18):
        # a fresh callable object per (re)arm: jax.jit caches traces per
        # function identity, so re-jitting the raw step directly would
        # never re-run the trace-time tripwire
        return jax.jit(lambda s, b, _r=_raw: _r(s, b))

    holder18 = {"f": fresh18()}

    def step18(s, b, _h=holder18):
        return _h["f"](s, b)

    armed18 = []

    def get_batch18(i, _h=holder18, _s=sched18, _a=armed18, _f=fresh18):
        if i == KILL_AT14 - 4 and not _a:   # step 2: mid-run, pre-checkpoint
            _a.append(i)
            _s.arm(3, after=0, mode="corrupt")
            _h["f"] = _f()                   # fresh trace sees the tripwire
        return batch_at14(i)

    retry18 = RetryPolicy(
        max_retries=2,
        reset=lambda _h=holder18, _f=fresh18: _h.__setitem__("f", _f()),
        verify=lambda out, _d=dist18: _d.abi.verify_clean(out, "train step"))
    ckdir18 = tempfile.mkdtemp(prefix="integrity_")
    report18 = run_supervised(
        step18, state18, get_batch18, checkpointer=Checkpointer(ckdir18),
        total_steps=4, checkpoint_every=2, max_restarts=1, retry=retry18)
    assert report18.steps_completed == 4, report18
    assert report18.restarts == 0, report18            # retried, not restarted
    assert report18.transport_retries == 1, report18
    assert report18.transport_escalations == 0, report18
    assert sched18.corrupted, impl18                   # the one-shot fired

    # oracle: unfailed run, SAME impl (plain backend), integrity still on
    disto18 = make_dist(mesh8, impl=impl18, integrity=True)
    stateo18 = train_loop.init_state(api14, key14, disto18)
    stepo18 = jax.jit(train_loop.make_train_step(api14, disto18, opt14))
    for s18 in range(4):
        stateo18, _m18 = stepo18(stateo18, batch_at14(s18))
    v18 = jax.tree.leaves(report18.final_state)
    o18 = jax.tree.leaves(stateo18)
    assert len(v18) == len(o18)
    for a18, b18 in zip(v18, o18):
        np.testing.assert_array_equal(np.asarray(a18), np.asarray(b18))
    shutil.rmtree(ckdir18, ignore_errors=True)
    print(f"  {impl18}: corrupt mid-zero1 -> detect -> retry, "
          "resume bitwise == oracle OK")

for impl18s in ("paxi", "minimal", "ompix"):
    sched18s = FaultSchedule()
    abi18s = C.pax_init(mesh, impl=make_faulty16(impl18s, mesh, sched18s))
    tp18s = abi18s.comm_from_axes(("model",), "tp")
    eng16.decode_sync = DecodeSync(abi18s, tp18s, 3, mesh)
    mon18s = HeartbeatMonitor(abi18s, tp18s, mesh, miss_threshold=2,
                              suspicion_ticks=1).install()
    sup18s = ServeSupervisor(eng16, monitor=mon18s, heartbeat_every=1,
                             wait_timeout_s=0.15, transport_retries=1)
    for r18s in mk_reqs16():
        eng16.submit(r18s)
    reqs18s = list(eng16.scheduler.waiting)
    while not all(s18s is not None and s18s.state == "decode"
                  for s18s in eng16.scheduler.slots):
        sup18s.step()
    mid18s = [len(r18s.out_tokens) for r18s in reqs18s]
    assert all(m18s > 0 for m18s in mid18s), mid18s   # genuinely mid-decode
    sched18s.arm(2, after=0, mode="drop")             # rank 2's link silent
    sup18s.drain()
    got18s = [r18s.out_tokens for r18s in reqs18s]
    assert got18s == want16, (impl18s, got18s, want16)
    assert sup18s.report.transport_retries == 1, sup18s.report
    assert sup18s.report.transport_escalations == 1, sup18s.report
    assert sup18s.report.failures == 1, sup18s.report
    assert abi18s.comms.info(eng16.decode_sync.comm).excludes == (2,)
    assert 2 in mon18s.confirmed         # observed via missed beats
    sup18s.report.assert_consistent()
    mon18s.uninstall()
    eng16.decode_sync.free()
    eng16.decode_sync = None
    print(f"  {impl18s}: dropped decode bcast -> timeout -> retry -> "
          "confirm -> shrink, replay bitwise == oracle OK")

# CI chaos-transport leg: with a corrupt/drop PAX_FAULT_SCHEDULE armed,
# the registry's faulty: prefix must surface the transport fault through
# the integrity/timeout contract and recover through the documented path
# (one-shot corrupt -> clean re-run; sticky drop -> reset + heal).
env18 = os.environ.get("PAX_FAULT_SCHEDULE")
se18 = None
if env18:
    abi18e = C.pax_init(mesh8, impl="faulty:paxi", integrity=True)
    se18 = fault_schedule_of(abi18e.backend)
    assert se18 is not None and se18.armed, env18
if se18 is not None and se18.mode in ("corrupt", "drop"):
    dpe18 = abi18e.comm_from_axes(("data",), "dp")
    xe18 = jnp.arange(32.0, dtype=jnp.float32) + 1.0
    plan18e = abi18e.allreduce_init(
        jax.ShapeDtypeStruct((32,), jnp.float32), C.PAX_SUM, dpe18)
    fe18 = shard_map(
        lambda v: abi18e.wait(plan18e.start(v), timeout_s=0.5),
        mesh=mesh8, in_specs=P(), out_specs=P())
    want18e = np.asarray(xe18) * 8.0
    for _ in range(se18.at_call):        # drive to just before the fault
        se18.on_call()
    if se18.mode == "corrupt":
        try:
            abi18e.verify_clean(fe18(xe18), "env chaos allreduce")
            raise AssertionError("env-armed corruption went undetected")
        except PaxError as e18x:
            assert e18x.code == PAX_ERR_DATA_CORRUPTION, e18x
        assert se18.corrupted             # one-shot: consumed by the hit
        np.testing.assert_array_equal(    # clean re-run, nothing wedged
            np.asarray(fe18(xe18)), want18e)
    else:                                 # drop: timeout -> reset -> heal
        t18e = _time.perf_counter()
        try:
            fe18(xe18)
            raise AssertionError("env-armed drop did not time out")
        except PaxError as e18x:
            assert e18x.code == PAX_ERR_TIMEOUT, e18x
        assert _time.perf_counter() - t18e >= 0.5
        plan18e.reset()                   # the post-timeout abort contract
        se18.dropping = False             # link heals; schedule disarmed
        se18.kill_rank = -1
        np.testing.assert_array_equal(np.asarray(fe18(xe18)), want18e)
    print(f"  env chaos schedule {env18!r}: transport fault surfaced and "
          "recovered OK")

print("BATTERY PASSED")
