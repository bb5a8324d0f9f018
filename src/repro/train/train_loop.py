"""Training step/loop builders.

Two step constructions per DESIGN.md:

* ``abi`` (default, ≤15B-class archs): a partial-manual ``shard_map`` over
  the dp axes; TP stays GSPMD (auto) inside.  Two gradient-sync layouts:

  - **ZeRO-1 flat** (``parallelism.zero1`` and ``init_state`` given the
    dist): the flat gradient vector is bucketed-**reduce-scattered**
    through the pooled nonblocking ABI path, the AdamW update runs on this
    rank's shard only (optimizer memory 1/dp), and the updated shard is
    bucketed-**all-gathered** back.  Moments live as (padded,) flat
    vectors sharded ``P(dp_axes)``: every rank holds its contiguous slice,
    the same slice the (transposed-split) bucketed reduce-scatter
    delivers.  The request pool recycles the bucket requests in place, so
    the steady-state step allocates no request objects.
  - **per-leaf DDP** (``init_state`` without a dist, the legacy layout):
    nonblocking ``iallreduce`` per leaf, moments TP-sharded like the
    params and dp-replicated.

  Optional bf16 wire compression; optional int8 via a ring-compressed
  backend.  The ABI carries all dp traffic either way.

* ``gspmd`` (300B-class: grok-1, nemotron-4): plain jit; params, grads and
  moments are FSDP x TP sharded via in_shardings (ZeRO-style memory
  scaling) and XLA inserts the collectives implicitly.

Both support gradient accumulation over microbatches (lax.scan) and buffer
donation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import PAX_SUM
from ..core.communicator import comm_rank_traced
from ..models.model import ModelApi
from ..optim import adamw
from ..optim.adamw import AdamState, AdamWConfig, FlatAdamState
from ..runtime.dist import DistContext, dp_comm_of
from ..runtime.sharding import use_rules
from .grad_sync import (allgather_params, pad_to, reduce_scatter_grads_finish,
                        reduce_scatter_grads_start)


class TrainState(NamedTuple):
    params: Any
    opt: AdamState
    step: jax.Array


class Metrics(NamedTuple):
    loss: jax.Array
    grad_norm: jax.Array


def _flat_opt_specs(dp_axes) -> FlatAdamState:
    """The one place the ZeRO-1 flat state's sharding is written down:
    moments shard over the dp axes, step replicated.  The error-feedback
    buffer is *per-rank* state (each rank's own wire-quantization residual),
    so it shards over the dp axes too — its global layout is (dp * padded,)
    (or a (dp,) dummy when compression is off), one full-length residual per
    rank."""
    dpP = P(tuple(dp_axes)) if dp_axes else P()
    return FlatAdamState(P(), dpP, dpP, dpP)


def _region_specs(state: TrainState, dp_axes) -> TrainState:
    """The abi step region's specs for the state, the same going in and
    coming out: params and step replicated over the dp axes, the optimizer
    state in its layout (ZeRO-1 flat or per-leaf)."""
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)
    opt = (_flat_opt_specs(dp_axes) if isinstance(state.opt, FlatAdamState)
           else rep(state.opt))
    return TrainState(rep(state.params), opt, P())


def _shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda v: isinstance(v, P))


def init_state(api: ModelApi, key, dist: Optional[DistContext] = None) -> TrainState:
    """Build the initial train state.

    With ``dist`` provided and ``parallelism.zero1`` set in abi mode, the
    optimizer state is the ZeRO-1 flat layout (moments for 1/dp of the
    parameters per rank); otherwise the classic per-leaf tree layout.

    The zero1 layout also (a) allocates the error-feedback buffer when bf16
    wire compression is configured (per-rank residuals, see
    :func:`_flat_opt_specs`) and (b) builds the persistent collective plans
    and their Startall groups for the bucketed round trip
    (``dist.zero1_plans``) — argument binding, handle conversion, recipe
    composition, group fusion AND the wire-kernel choice (the fused Pallas
    flatten/bucket pack when the registry + layout allow it, the lax
    pipeline otherwise — ``Zero1Plans.wire_kernel`` records which) happen
    here, once, not per step.

    Re-initialization is **layout-transparent** (the ABI's layout-keyed
    plan cache): re-init with the same (padded, dp, buckets, wire) layout
    keeps the live plans/groups untouched — zero new request slots — while
    a genuine layout change (re-sharding, elastic dp, bucket retune)
    retires the old slots and re-plans.

    In abi mode the state is committed to ``dist.mesh`` with the shardings
    the step returns, so the jitted step sees the same input shardings on
    every call and compiles once."""
    params = api.init(key)
    par = api.cfg.parallelism
    if dist is not None and par.grad_sync == "abi" and par.zero1:
        buckets = max(par.zero1_buckets, 1)
        with_ef = par.grad_compression == "bf16"
        # made in place on each device: the full-length moments never sit
        # on one device
        opt = jax.jit(
            lambda: adamw.init_flat_global(
                params, dist.dp_size, buckets=buckets, with_ef=with_ef),
            out_shardings=_shardings(dist.mesh, _flat_opt_specs(dist.dp_axes)),
        )()
        from .grad_sync import build_zero1_plans, zero1_wire_dtype
        old = dist.zero1_plans
        if old is None or not old.matches(
                opt.m.shape[0], dist.dp_size, buckets,
                zero1_wire_dtype(par.grad_compression), par.grad_compression):
            # genuine layout change: retire the old plans' request slots
            # before rebuilding, or every re-init leaks slots
            dist.drop_zero1_plans()
            dist.zero1_plans = build_zero1_plans(
                dist, opt.m.shape[0], buckets, par.grad_compression)
    else:
        opt = adamw.init_tree(params)
    state = TrainState(params, opt, jnp.zeros((), jnp.int32))
    if dist is None or par.grad_sync != "abi":
        return state
    return jax.device_put(
        state, _shardings(dist.mesh, _region_specs(state, dist.dp_axes)))


def _microbatched_grads(loss_fn, params, batch, n_micro: int):
    """Gradient accumulation via scan; returns (mean_loss, grads)."""
    if n_micro <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def reshape(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    mbatches = jax.tree.map(reshape, batch)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        loss_acc, g_acc = carry
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        g_acc = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32), g_acc, g)
        return (loss_acc + loss, g_acc), None

    (loss_sum, gsum), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), mbatches)
    inv = 1.0 / n_micro
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, gsum)


def sync_grads_abi(dist: DistContext, grads, compression: Optional[str],
                   grad_specs=None):
    """Per-leaf nonblocking all-reduce over the dp communicator (each leaf is
    a bucket; requests are issued together and awaited together so the
    scheduler can overlap them).

    ``grad_specs`` (the TP param specs) pins each leaf's model-axis sharding
    through the collective: without the constraint GSPMD lowers the dp psum
    of a TP-sharded gradient as all-gather + full all-reduce + re-slice —
    16x the wire bytes (§Perf qwen2-moe iteration 4 finding).
    """
    abi, comm = dp_comm_of(dist, compression == "int8")
    dp = dist.dp_size
    leaves, treedef = jax.tree.flatten(grads)
    specs = (jax.tree.leaves(grad_specs, is_leaf=lambda v: isinstance(v, P))
             if grad_specs is not None else [None] * len(leaves))

    def pin(x, spec):
        if spec is None:
            return x
        try:
            return jax.lax.with_sharding_constraint(x, _trim_spec(spec, x.ndim))
        except Exception:
            return x

    wires = [l.astype(jnp.bfloat16) if compression == "bf16" else l for l in leaves]
    wires = [pin(w, s) for w, s in zip(wires, specs)]
    reqs = [abi.iallreduce(w, PAX_SUM, comm) for w in wires]
    summed = abi.waitall(reqs)
    out = [pin(s, sp).astype(jnp.float32) / dp for s, sp in zip(summed, specs)]
    return jax.tree.unflatten(treedef, out)


def _trim_spec(spec: P, rank: int) -> P:
    parts = tuple(spec)[:rank]
    return P(*parts)


# ---------------------------------------------------------------------------
# ABI mode
# ---------------------------------------------------------------------------
def make_train_step_abi(
    api: ModelApi,
    dist: DistContext,
    opt_cfg: AdamWConfig,
    *,
    schedule: Optional[Callable] = None,
):
    cfg = api.cfg
    par = cfg.parallelism
    n_micro = max(par.microbatch, 1)
    compression = par.grad_compression
    buckets = max(par.zero1_buckets, 1)
    # TP shardings of the gradients (== param specs without fsdp axes)
    grad_specs = api.param_specs(fsdp=None, tp=dist.tp_axis)

    def body(params, opt: AdamState, step, batch):
        with use_rules(dist.rules):
            loss, grads = _microbatched_grads(
                lambda p, b: api.loss_fn(p, b, dist), params, batch, n_micro)
            grads = sync_grads_abi(dist, grads, compression, grad_specs)
            lr_scale = schedule(step) if schedule is not None else jnp.float32(1.0)
            new_params, new_opt, gnorm = adamw.update_tree(
                opt_cfg, grads, opt, params, lr_scale)
            loss = dist.abi.allreduce(loss, PAX_SUM, dist.dp_comm) / dist.dp_size
        return new_params, new_opt, loss, gnorm

    def body_zero1(params, opt: FlatAdamState, step, batch):
        """Explicit ZeRO-1 round trip (the ROADMAP wiring): one
        reduce-scatter *group* start -> shard-local AdamW -> one all-gather
        group start/wait, riding the Startall plan groups built at
        ``init_state`` (``dist.zero1_plans``; pooled nonblocking ``i*``
        requests as the fallback).  The reduce-scatter group is issued
        BEFORE the param flatten/rank-slice compute and waited after, so
        the in-flight fused collective overlaps the independent work (and,
        across jitted steps, the next microbatch's backward — XLA's
        latency-hiding scheduler sees the start/wait dataflow gap).  With
        bf16 wire compression the per-rank error-feedback residual
        (``opt.ef``) is folded into the next step's gradient and refreshed
        from this step's quantization error."""
        dp = dist.dp_size
        plans = dist.zero1_plans
        with use_rules(dist.rules):
            loss, grads = _microbatched_grads(
                lambda p, b: api.loss_fn(p, b, dist), params, batch, n_micro)
            pad = adamw.zero1_pad_multiple(dp, buckets)
            flat_g = pad_to(adamw.flatten(grads), pad)
            n_flat = sum(int(l.size) for l in jax.tree.leaves(grads))
            # error feedback: opt.ef is this rank's full-length residual
            # exactly when compression is on (a (1,)-dummy otherwise)
            ef = opt.ef if opt.ef.shape[0] == flat_g.shape[0] else None
            pending, new_ef = reduce_scatter_grads_start(
                dist, flat_g, compression=compression, buckets=buckets,
                ef=ef, plans=plans)
            # overlapped with the in-flight reduce-scatter group: this
            # rank's contiguous param slice (same layout as g_shard and as
            # the P(dp_axes)-sharded moment vectors) depends only on params
            flat_p = pad_to(adamw.flatten(params), pad)
            shard_len = flat_p.shape[0] // dp
            r = comm_rank_traced(dist.abi.comms.info(dist.dp_comm))
            p_shard = jax.lax.dynamic_slice_in_dim(flat_p, r * shard_len, shard_len)
            g_shard = reduce_scatter_grads_finish(pending)
            # ||mean grad||²: each element lives on exactly one rank's shard
            gnorm = jnp.sqrt(dist.abi.allreduce(
                jnp.sum(jnp.square(g_shard)), PAX_SUM, dist.dp_comm))
            lr_scale = schedule(step) if schedule is not None else jnp.float32(1.0)
            new_p_shard, new_opt = adamw.update_flat_shard(
                opt_cfg, g_shard, opt, p_shard, gnorm, lr_scale)
            if ef is not None and new_ef is not None:
                new_opt = new_opt._replace(ef=new_ef)
            p_full = allgather_params(dist, new_p_shard, buckets=buckets,
                                      plans=plans)
            new_params = adamw.unflatten_like(p_full[:n_flat], params)
            loss = dist.abi.allreduce(loss, PAX_SUM, dist.dp_comm) / dp
        return new_params, new_opt, loss, gnorm

    def step_fn(state: TrainState, batch):
        sp = _region_specs(state, dist.dp_axes)
        f = dist.abi.shard_region(
            body_zero1 if isinstance(state.opt, FlatAdamState) else body,
            # step passed explicitly: closures over tracers are
            # illegal inside shard_map bodies
            in_specs=(sp.params, sp.opt, sp.step,
                      jax.tree.map(lambda _: P(dist.dp_axes), batch)),
            out_specs=(sp.params, sp.opt, P(), P()),
            axis_names=set(dist.dp_axes),
        )
        new_params, new_opt, loss, gnorm = f(state.params, state.opt, state.step, batch)
        return TrainState(new_params, new_opt, state.step + 1), Metrics(loss, gnorm)

    return step_fn


# ---------------------------------------------------------------------------
# GSPMD mode
# ---------------------------------------------------------------------------
def make_train_step_gspmd(
    api: ModelApi,
    dist: Optional[DistContext],
    opt_cfg: AdamWConfig,
    *,
    schedule: Optional[Callable] = None,
):
    cfg = api.cfg
    n_micro = max(cfg.parallelism.microbatch, 1)
    rules = dist.rules if dist is not None else None

    def step_fn(state: TrainState, batch):
        with use_rules(rules):
            loss, grads = _microbatched_grads(
                lambda p, b: api.loss_fn(p, b, dist), state.params, batch, n_micro)
            lr_scale = schedule(state.step) if schedule is not None else 1.0
            new_params, new_opt, gnorm = adamw.update_tree(
                opt_cfg, grads, state.opt, state.params, lr_scale)
        return TrainState(new_params, new_opt, state.step + 1), Metrics(loss, gnorm)

    return step_fn


def make_train_step(api: ModelApi, dist, opt_cfg: AdamWConfig, **kw):
    if api.cfg.parallelism.grad_sync == "abi" and dist is not None:
        return make_train_step_abi(api, dist, opt_cfg, **kw)
    return make_train_step_gspmd(api, dist, opt_cfg, **kw)


# ---------------------------------------------------------------------------
# elastic-dp recovery (the fault-tier consumer)
# ---------------------------------------------------------------------------
def with_failure_probe(dist: DistContext, step_fn: Callable) -> Callable:
    """Prepend a host-side fault-tier probe to a (possibly jitted) step_fn.

    A compiled step cannot raise on a later rank death — injection and
    detection live at dispatch time in the single-controller simulation —
    so the supervised loop's failure notification is an agreement on the
    data-parallel communicator before each launch: ``comm_agree`` raises
    ``PAX_ERR_PROC_FAILED`` the moment the failure detector reports an
    unacknowledged death (the ULFM notification idiom)."""

    def probed(state, batch):
        dist.abi.comm_agree(1, dist.dp_comm)
        return step_fn(state, batch)

    return probed


def rebalance_batch(batch, dp: int):
    """Trim a global batch's leading dim to the largest multiple of ``dp``
    (identity when ``dp`` already divides it).

    The uneven-shard recovery mode keeps ALL survivors (dp=7 instead of a
    power-of-two trim to 4), so the fixed global batch no longer divides
    the dp extent; the ``shard_map`` over ``P(dp_axes)`` requires it to.
    Trimming happens OUTSIDE the jitted step — host-side, before tracing —
    so the compiled step sees a clean ``(B', ...)`` with ``dp | B'``.  The
    dropped rows are the batch tail, deterministically, so an oracle run
    using the same function sees the same data."""
    def trim(x):
        b = (x.shape[0] // dp) * dp
        if b == 0:
            raise ValueError(f"batch dim {x.shape[0]} < dp={dp}: nothing to shard")
        return x if b == x.shape[0] else x[:b]

    return jax.tree.map(trim, batch)


def elastic_recovery_policy(api: ModelApi, opt_cfg: AdamWConfig, dist: DistContext,
                            key, *, impl=None, schedule=None, tools=(),
                            uneven_shards: bool = False,
                            integrity: Optional[bool] = None):
    """The canonical ``RecoveryPolicy`` for elastic-dp training.

    After ``run_supervised``'s fault-tier walk (revoke → ack → get_failed →
    agree → shrink) the ``rebuild`` callback re-derives the training world:

    * a dense mesh over the survivors (``survivor_mesh``), trimmed to the
      largest power-of-two dp extent so batch and flat-layout divisibility
      survive arbitrary casualty counts (8 ranks − 1 dead → dp=4) — or,
      with ``uneven_shards=True``, kept at the full survivor count (dp=7)
      with the global batch rebalanced per step via
      :func:`rebalance_batch` (host-side trim to a dp multiple; use the
      per-leaf DDP optimizer layout — the zero1 flat layout re-pads to the
      new dp and cannot restore an old checkpoint shape);
    * a fresh ``DistContext`` over it (``impl`` names the *recovered*
      backend — typically the plain implementation underneath the
      fault-injection wrapper);
    * ``init_state`` on the new dist, which re-plans the zero1 collective
      plans through the layout-keyed cache (a genuine layout change retires
      the old slots; an identical layout reuses live plans);
    * the new step_fn (jitted, failure-probed) and the restore specs for
      ``Checkpointer.restore(mesh=new_mesh, specs=...)``.

    Ranks are linearized mesh positions, so this assumes the dp axis leads
    the mesh (tp groups must survive intact — elastic *data* parallelism).
    ``policy.dist`` is updated to the rebuilt context, so a second failure
    recovers from the already-shrunk world.  ``integrity`` carries the
    checksummed-wire mode into the rebuilt context — a recovered world
    keeps the detection guarantees of the one it replaces (default: the
    original ``dist``'s setting).
    """
    from ..runtime.dist import make_dist, survivor_mesh
    from ..runtime.fault import RecoveryPolicy, RecoveryTarget

    def rebuild(survivors: int, failed: tuple) -> RecoveryTarget:
        mesh = survivor_mesh(policy.dist.mesh, failed)
        names = tuple(mesh.axis_names)
        dp_avail = mesh.shape[names[0]]
        if uneven_shards:
            dp_new = dp_avail       # keep every survivor; rebalance batches
        else:
            dp_new = 1 << (dp_avail.bit_length() - 1)
            if dp_new != dp_avail:
                mesh = jax.sharding.Mesh(mesh.devices[:dp_new], names)
        keep_integrity = (dist.abi.integrity if integrity is None
                          else integrity)
        new_dist = make_dist(mesh, impl=impl, tools=tools,
                             integrity=keep_integrity)
        state_like = init_state(api, key, new_dist)
        jstep = jax.jit(make_train_step(api, new_dist, opt_cfg,
                                        schedule=schedule))
        if uneven_shards:
            # trim outside the jitted step: the shard_map's P(dp_axes)
            # in_spec needs dp | batch, and tracing must see the final shape
            jstep = (lambda _j, _dp: lambda state, batch:
                     _j(state, rebalance_batch(batch, _dp)))(jstep, dp_new)
        step_fn = with_failure_probe(new_dist, jstep)
        par = api.cfg.parallelism
        zero1 = par.grad_sync == "abi" and par.zero1
        specs = state_specs(api, "abi",
                            dp_axes=new_dist.dp_axes if zero1 else None)
        policy.dist = new_dist
        return RecoveryTarget(step_fn, state_like, mesh=mesh, specs=specs)

    policy = RecoveryPolicy(dist=dist, rebuild=rebuild)
    return policy


# ---------------------------------------------------------------------------
# state sharding specs (for jit in_shardings / checkpoint layouts)
# ---------------------------------------------------------------------------
def state_specs(api: ModelApi, mode: str, fsdp="data", tp="model", dp_axes=None):
    """PartitionSpec pytree for TrainState.

    * abi mode: params TP-sharded only (dp-replicated); moments likewise in
      the per-leaf layout, or — with ``dp_axes`` given for the ZeRO-1 flat
      layout — (padded,) flat vectors sharded over the dp axes;
    * gspmd mode: params/moments FSDP x TP sharded (param specs already
      carry the fsdp axes).
    """
    pspecs = api.param_specs(fsdp=fsdp, tp=tp) if mode == "gspmd" else (
        api.param_specs(fsdp=None, tp=tp))
    if mode == "abi" and dp_axes is not None:
        return TrainState(pspecs, _flat_opt_specs(dp_axes), P())
    return TrainState(
        pspecs,
        AdamState(P(), jax.tree.map(lambda s: s, pspecs),
                  jax.tree.map(lambda s: s, pspecs)),
        P(),
    )
