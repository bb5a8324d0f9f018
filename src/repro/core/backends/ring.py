"""ring — an algorithmic ABI-native backend: explicit ring collectives.

Same handle convention as :mod:`paxi` (it is a second *native* implementation
of the standard ABI — the ecosystem the paper wants: N interchangeable
implementations behind one ABI).  Collectives lower to explicit
``ppermute`` ring schedules instead of single XLA collective ops:

* ring reduce-scatter + ring all-gather == bandwidth-optimal all-reduce,
  with per-step traffic visible in the HLO (useful for the roofline tool
  and for overlap experiments — each hop is an independently schedulable
  ``collective-permute``);
* optional wire compression (``compress="bf16"|"int8"``): payload quantized
  per hop, accumulated in the original dtype.  int8 uses a per-hop absmax
  scale.  This is the gradient-compression substrate (train/compression.py
  adds error feedback on top).  The compressed wire covers the SUM prefix
  scans too: ``ring_scan_sum`` quantizes each forwarded contribution, and
  multi-axis communicators use the hierarchical ``ring_scan_sum_multi``
  schedule (minor-axis scan + ``ring_allreduce_sum`` row totals + major-axis
  scan of the totals) instead of falling back to the generic fold.

Multi-axis communicators reduce hierarchically (axis by axis) — the classic
2D-torus schedule.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .. import handles as H
from . import _lax
from .paxi import PaxiBackend, uniform_payload


def _quantize(x, compress: Optional[str]):
    if compress is None:
        return x, None
    if compress == "bf16":
        return x.astype(jnp.bfloat16), None
    if compress == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q, scale
    raise ValueError(f"unknown compression {compress!r}")


def _dequantize(q, scale, dtype, compress: Optional[str]):
    if compress is None:
        return q
    if compress == "bf16":
        return q.astype(dtype)
    return q.astype(dtype) * scale


def ring_reduce_scatter(x, axis_name: str, compress: Optional[str] = None):
    """Returns this rank's fully-reduced chunk (chunk index == rank).

    ``x`` must have leading dim divisible by the axis size. S-1 hops.
    """
    S = lax.axis_size(axis_name)
    if S == 1:
        return x
    i = lax.axis_index(axis_name)
    n = x.shape[0]
    assert n % S == 0, f"ring reduce_scatter needs {S} | {n}"
    c = n // S
    perm = [(s, (s + 1) % S) for s in range(S)]

    def chunk_at(idx):
        return lax.dynamic_slice_in_dim(x, idx * c, c, axis=0)

    travel = chunk_at((i - 1) % S)
    for t in range(S - 1):
        q, scale = _quantize(travel, compress)
        q = lax.ppermute(q, axis_name, perm)
        if scale is not None:
            scale = lax.ppermute(scale, axis_name, perm)
        received = _dequantize(q, scale, x.dtype, compress)
        travel = received + chunk_at((i - 2 - t) % S)
    return travel  # chunk index == own rank


def ring_reduce_scatter_fused(x, axis_name: str, compress: str,
                              interpret: bool):
    """:func:`ring_reduce_scatter` on the fused Pallas wire
    (:mod:`repro.kernels.ring_wire`): the traveling block stays *quantized*
    between hops and each hop's dequantize + accumulate + re-quantize is one
    kernel pass — one read of the traveling block, one write of the outgoing
    block, instead of three materialized lax intermediates.  Same
    quantization-point sequence as the lax schedule (quantize at every send,
    plain dequant-accumulate after the last hop), so the bf16 wire is
    bitwise-identical; int8 upgrades the global absmax scale to per-block
    scales (strictly finer — bounded in the battery, section 12).

    Only called from plan closures: eligibility (compressed wire, f32,
    WIRE_BLOCK-divisible chunk, platform) is decided at plan time by
    ``RingBackend._wire_kernel_axes``.
    """
    from ...kernels.ring_wire import ops as wire_ops

    S = lax.axis_size(axis_name)
    if S == 1:
        return x
    i = lax.axis_index(axis_name)
    n = x.shape[0]
    assert n % S == 0, f"ring reduce_scatter needs {S} | {n}"
    c = n // S
    perm = [(s, (s + 1) % S) for s in range(S)]

    def chunk_at(idx):
        return lax.dynamic_slice_in_dim(x, idx * c, c, axis=0)

    q, scales = wire_ops.quant(chunk_at((i - 1) % S), compress,
                               interpret=interpret)
    for t in range(S - 1):
        q = lax.ppermute(q, axis_name, perm)
        if scales is not None:
            scales = lax.ppermute(scales, axis_name, perm)
        local = chunk_at((i - 2 - t) % S)
        if t < S - 2:
            q, scales = wire_ops.hop_add_quant(q, scales, local, compress,
                                               interpret=interpret)
        else:
            return wire_ops.hop_accum(q, scales, local, compress,
                                      interpret=interpret)


def ring_allgather(x, axis_name: str):
    """Inverse of ring_reduce_scatter: collect every rank's chunk. S-1 hops."""
    S = lax.axis_size(axis_name)
    if S == 1:
        return x
    i = lax.axis_index(axis_name)
    c = x.shape[0]
    perm = [(s, (s + 1) % S) for s in range(S)]
    out = jnp.zeros((S * c,) + x.shape[1:], dtype=x.dtype)
    out = lax.dynamic_update_slice_in_dim(out, x, i * c, axis=0)
    travel = x
    for t in range(S - 1):
        travel = lax.ppermute(travel, axis_name, perm)
        src = (i - 1 - t) % S  # who produced the chunk we just received
        out = lax.dynamic_update_slice_in_dim(out, travel, src * c, axis=0)
    return out


def ring_scan_sum(x, axis_name: str, inclusive: bool = True,
                  compress: Optional[str] = None):
    """SUM prefix over ranks via S-1 explicit hops: every hop forwards the
    neighbour's contribution one step; rank i accumulates the terms with
    source index < i (masked add).  Exclusive scan leaves rank 0's input
    unchanged — the ABI-wide exscan convention (MPI: undefined).

    With ``compress`` the traveling contribution is quantized per hop
    exactly like :func:`ring_reduce_scatter`'s wire; accumulation stays in
    the original dtype.  Error compounds with hop count (bounded in the
    multidev battery, section 6)."""
    S = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    if S == 1:
        return x
    perm = [(s, (s + 1) % S) for s in range(S)]
    acc = x if inclusive else jnp.where(i == 0, x, jnp.zeros_like(x))
    travel = x
    for t in range(S - 1):
        q, scale = _quantize(travel, compress)
        q = lax.ppermute(q, axis_name, perm)
        if scale is not None:
            scale = lax.ppermute(scale, axis_name, perm)
        travel = _dequantize(q, scale, x.dtype, compress)
        # after hop t, rank i holds rank (i-1-t)'s contribution
        acc = acc + jnp.where(i >= t + 1, travel, jnp.zeros_like(travel))
    return acc


def ring_allreduce_sum(x, axis_name: str, compress: Optional[str] = None):
    """Divisibility-free SUM all-reduce: S-1 broadcast-add hops (each rank's
    contribution travels the whole ring once).  Used by the hierarchical
    multi-axis scan for row totals, where the payload need not split into
    rank chunks.  Wire compressed per hop like the other ring schedules."""
    S = lax.axis_size(axis_name)
    if S == 1:
        return x
    perm = [(s, (s + 1) % S) for s in range(S)]
    acc = x
    travel = x
    for t in range(S - 1):
        q, scale = _quantize(travel, compress)
        q = lax.ppermute(q, axis_name, perm)
        if scale is not None:
            scale = lax.ppermute(scale, axis_name, perm)
        travel = _dequantize(q, scale, x.dtype, compress)
        acc = acc + travel
    return acc


def ring_scan_sum_multi(x, axes, inclusive: bool = True,
                        compress: Optional[str] = None):
    """Hierarchical SUM prefix over a multi-axis communicator, all on the
    ring wire (compression included): the prefix over linearized (row-major)
    rank splits as

        scan(x)[iA, iB]  =  scan_minor(x within row iA)
                          + sum of all full rows jA < iA,

    where the row totals ride :func:`ring_allreduce_sum` and the major-axis
    prefix is a :func:`ring_scan_sum` of the totals.  The exclusive variant
    keeps the ABI convention (linearized rank 0 returns its input)."""
    axes = tuple(axes)
    if len(axes) == 1:
        return ring_scan_sum(x, axes[0], inclusive, compress)
    tail = axes[1:]
    row_total = x
    for a in reversed(tail):
        row_total = ring_allreduce_sum(row_total, a, compress)
    # true-exclusive prefix of the row totals over the major axis
    major_excl = ring_scan_sum(row_total, axes[0], True, compress) - row_total
    inner_incl = ring_scan_sum_multi(x, tail, True, compress)
    if inclusive:
        return inner_incl + major_excl
    r = _lax.rank(axes)  # linearized rank 0 keeps its input (ABI convention)
    return jnp.where(r == 0, x, inner_incl - x + major_excl)


class RingBackend(PaxiBackend):
    """ABI-native backend with explicit ring schedules for SUM collectives.

    Non-SUM ops and non-flattenable payloads fall back to the paxi lowering
    (an implementation is free to mix algorithms per op — MPI
    implementations do exactly this).

    ``allreduce`` is deliberately **not** exported (``ABI_DROPPED``): the
    hand-written RS+AG composition this backend used to carry is exactly the
    spec's emulation recipe, so tiered negotiation now composes the ring
    reduce-scatter and ring all-gather below — the backend shrank while its
    coverage (and the compressed wire) stayed.  Reduce-scatter and
    all-gather gained hierarchical multi-axis schedules (forward/reverse
    axis order, chunk index == linearized rank) so the composed all-reduce
    still runs the ring wire — compression included — on multi-axis
    communicators.
    """

    name = "ring"

    ABI_DROPPED = frozenset({"allreduce"})

    def __init__(self, *args, compress: Optional[str] = None, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.compress = compress

    def _axis_sizes(self, axes) -> list[int]:
        mesh = self.comms.mesh
        return [mesh.shape[a] if mesh else 1 for a in axes]

    # -- fused-wire kernel selection (plan time only) -----------------------
    def _wire_kernel_mode(self) -> str:
        """``"pallas"`` iff the fused ring-wire kernels can carry this
        backend's compressed wire on the current platform (kernel registry
        answer); plain-ring and unknown platforms stay ``"lax"``."""
        if self.compress is None:
            return "lax"
        from ...kernels import kernel_mode
        return kernel_mode("ring_wire")

    def _wire_kernel_axes(self, shape, dtype, axes) -> list[bool]:
        """Per-axis fused-kernel eligibility for a reduce-scatter plan bound
        to ``shape``/``dtype``: the hop chunk along each axis (after the
        preceding axes' reductions shrank the leading dim) must satisfy
        :func:`repro.kernels.ring_wire.wire_eligible`.  Ineligible axes run
        the lax schedule — selection is per hop-loop, not all-or-nothing."""
        if self._wire_kernel_mode() != "pallas":
            return [False] * len(axes)
        from ...kernels.ring_wire import ops as wire_ops
        trailing = math.prod(shape[1:]) if len(shape) > 1 else 1
        rows = shape[0]
        flags = []
        for S in self._axis_sizes(axes):
            if S <= 1:
                flags.append(False)
            else:
                flags.append(wire_ops.wire_eligible(
                    ((rows // S) * trailing,), dtype, self.compress))
            rows //= max(S, 1)
        return flags

    def capability(self, entry):
        """Extend the per-entry report with the wire-kernel source: which
        implementation a plan bound to an eligible payload would run.  The
        fused kernels exist only for the reduce-scatter hop loop; every
        other wire-bearing entry (and plain ring) reports ``"lax"`` — the
        fallback the battery keeps exercised."""
        info = super().capability(entry)
        if entry.name in ("reduce_scatter", "allgather", "scan", "exscan"):
            info["wire_kernel"] = (self._wire_kernel_mode()
                                   if entry.name == "reduce_scatter"
                                   else "lax")
        return info

    def wire_pad_multiple(self) -> int:
        """Padding granule for emulation recipes: with the fused wire
        active, rounding invented padding up to WIRE_BLOCK keeps the
        composed all-reduce's reduce-scatter leg kernel-eligible."""
        if self._wire_kernel_mode() != "pallas":
            return 1
        from ...kernels.ring_wire import ops as wire_ops
        return wire_ops.WIRE_BLOCK

    def reduce_scatter(self, x, op: int, comm: int, axis: int = 0):
        axes = self.comm_axes(comm)
        if op != H.PAX_SUM or not axes or axis != 0:
            return super().reduce_scatter(x, op, comm, axis=axis)
        if x.shape[0] % math.prod(self._axis_sizes(axes)):
            return super().reduce_scatter(x, op, comm, axis=axis)
        for a in axes:  # forward axis order: chunk == linearized rank
            x = ring_reduce_scatter(x, a, self.compress)
        return x

    def allgather(self, x, comm: int, axis: int = 0):
        axes = self.comm_axes(comm)
        if not axes or axis != 0:
            return super().allgather(x, comm, axis=axis)
        for a in reversed(axes):  # reverse order: inverse of reduce_scatter
            x = ring_allgather(x, a)
        return x

    def scan(self, x, op: int, comm: int):
        axes = self.comm_axes(comm)
        if op != H.PAX_SUM or not axes:
            return super().scan(x, op, comm)
        return ring_scan_sum_multi(x, axes, inclusive=True,
                                   compress=self.compress)

    def exscan(self, x, op: int, comm: int):
        axes = self.comm_axes(comm)
        if op != H.PAX_SUM or not axes:
            return super().exscan(x, op, comm)
        return ring_scan_sum_multi(x, axes, inclusive=False,
                                   compress=self.compress)

    # -- persistent plans: decide ring-vs-fallback once from the example ----
    def plan_reduce_scatter(self, x, op: int, comm: int, axis: int = 0):
        axes = self.comm_axes(comm)
        if (op != H.PAX_SUM or not axes or axis != 0
                or tuple(x.shape)[0] % math.prod(self._axis_sizes(axes))):
            return super().plan_reduce_scatter(x, op, comm, axis)
        compress = self.compress
        # kernel-vs-lax decided HERE, from the bound shape/dtype/platform —
        # the run closure carries a fixed per-axis schedule, callers never
        # see the choice (capabilities() reports it as `wire_kernel`)
        fused = self._wire_kernel_axes(tuple(x.shape), x.dtype, axes)
        if any(fused):
            from ...kernels.ring_wire import ops as wire_ops
            interp = wire_ops.interpret_on()

        def run(x):
            for a, k in zip(axes, fused):  # forward order: chunk == rank
                x = (ring_reduce_scatter_fused(x, a, compress, interp)
                     if k else ring_reduce_scatter(x, a, compress))
            return x

        return run

    def plan_allgather(self, x, comm: int, axis: int = 0):
        axes = self.comm_axes(comm)
        if not axes or axis != 0:
            return super().plan_allgather(x, comm, axis)

        def run(x):
            for a in reversed(axes):  # inverse of reduce_scatter
                x = ring_allgather(x, a)
            return x

        return run

    # -- plan-group hooks: fuse the members into ONE ring schedule whose
    # wire carries all buckets side by side (stacked on a trailing member
    # axis, so the leading axis keeps the rank-chunk layout the hops slice).
    # Compression quantizes the fused block per hop — one absmax scale
    # covers every member's traveling contribution, and the group pays one
    # set of S-1 hops instead of N.
    def plan_group_reduce_scatter(self, bounds):
        _, op, comm, axis = bounds[0]
        axes = self.comm_axes(comm)
        u = uniform_payload(bounds, min_ndim=1)
        if (u is None or op != H.PAX_SUM or not axes or axis != 0
                or u[0][0] % math.prod(self._axis_sizes(axes))):
            return super().plan_group_reduce_scatter(bounds)
        compress = self.compress
        n = len(bounds)
        # same plan-time selection as the single plan, against the *stacked*
        # payload the group wire actually carries
        stacked = (u[0][0], n) + tuple(u[0][1:])
        fused = self._wire_kernel_axes(stacked, u[1], axes)
        if any(fused):
            from ...kernels.ring_wire import ops as wire_ops
            interp = wire_ops.interpret_on()

        def run(xs):
            x = jnp.stack(xs, axis=1)  # (rows, members, ...): one fused wire
            for a, k in zip(axes, fused):  # forward order: chunk == rank
                x = (ring_reduce_scatter_fused(x, a, compress, interp)
                     if k else ring_reduce_scatter(x, a, compress))
            return [x[:, i] for i in range(n)]

        return run

    def plan_group_allgather(self, bounds):
        _, comm, axis = bounds[0]
        axes = self.comm_axes(comm)
        if uniform_payload(bounds, min_ndim=1) is None or not axes or axis != 0:
            return super().plan_group_allgather(bounds)
        n = len(bounds)

        def run(xs):
            x = jnp.stack(xs, axis=1)
            for a in reversed(axes):  # inverse of reduce_scatter
                x = ring_allgather(x, a)
            return [x[:, i] for i in range(n)]

        return run
