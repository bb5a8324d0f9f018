"""Shared ``jax.lax`` lowering of the abstract collectives.

All backends that map to XLA collectives funnel through these helpers.
Axes are ordered mesh-axis tuples (row-major rank order — see
``communicator.comm_rank_traced``):

* ``reduce_scatter`` applies per-axis scatters in *forward* axis order and
* ``all_gather`` applies per-axis gathers in *reverse* axis order,

so that chunk index == linearized communicator rank, and the two compose to
an all-reduce exactly like a ring implementation would.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..emulation import prefix_fold

#: jaxpr primitives that put payload on the inter-chip wire — the canonical
#: list for traffic classification (launch/hlo_analysis.wire_breakdown
#: separates these from the HBM-side intermediates a fused kernel removes)
WIRE_PRIMITIVES = frozenset({
    "ppermute", "psum", "all_gather", "psum_scatter", "all_to_all",
})


def rank(axes: Sequence[str]):
    if not axes:
        return jnp.int32(0)
    r = lax.axis_index(axes[0])
    for a in axes[1:]:
        r = r * lax.axis_size(a) + lax.axis_index(a)
    return r


def _cpu_safe_dtype(x):
    """XLA-CPU's AllReducePromotion pass crashes on sub-f32 float all-reduce /
    reduce-scatter emitted by shard_map (CreateBinary(copy) in CloneAllReduce).
    On the CPU dry-run container we upcast the wire to f32 and downcast after;
    on TPU (the target) this shim is inert and the wire stays bf16.
    EXPERIMENTS.md §Dry-run footnotes the 2x all-reduce-byte inflation."""
    import jax

    if jax.default_backend() != "cpu":
        return x, None
    if jnp.issubdtype(x.dtype, jnp.floating) and jnp.dtype(x.dtype).itemsize < 4:
        return x.astype(jnp.float32), x.dtype
    return x, None


def psum(x, axes: Sequence[str]):
    if not axes:
        return x
    xw, orig = _cpu_safe_dtype(x)
    out = lax.psum(xw, tuple(axes))
    return out.astype(orig) if orig is not None else out


def pmax(x, axes: Sequence[str]):
    return lax.pmax(x, tuple(axes)) if axes else x


def pmin(x, axes: Sequence[str]):
    return lax.pmin(x, tuple(axes)) if axes else x


def allreduce_generic(x, fn: Callable, axes: Sequence[str]):
    """All-reduce for ops XLA has no wire-reduction for (PROD, bitwise,
    logical, MINLOC/MAXLOC, user callbacks): all-gather + local fold,
    applied per axis.  This mirrors how MPI implementations lower exotic
    ops to pt2pt; the ABI makes no claim that every op is wire-native."""
    for a in axes:
        g = lax.all_gather(x, a, axis=0, tiled=False)  # (axis_size, *x.shape)
        n = g.shape[0]
        acc = g[0]
        for i in range(1, n):
            acc = fn(acc, g[i])
        x = acc
    return x


def allgather(x, axes: Sequence[str], axis: int = 0, tiled: bool = True):
    for a in reversed(tuple(axes)):
        x = lax.all_gather(x, a, axis=axis, tiled=tiled)
    return x


def reduce_scatter_sum(x, axes: Sequence[str], axis: int = 0):
    xw, orig = _cpu_safe_dtype(x)
    for a in tuple(axes):
        xw = lax.psum_scatter(xw, a, scatter_dimension=axis, tiled=True)
    return xw.astype(orig) if orig is not None else xw


def reduce_scatter_generic(x, fn: Callable, axes: Sequence[str], axis: int = 0):
    """Generic-op reduce-scatter: all-reduce then slice own chunk."""
    x = allreduce_generic(x, fn, axes)
    r = rank(axes)
    import math

    total = math.prod(lax.axis_size(a) for a in axes) if axes else 1
    chunk = x.shape[axis] // total
    return lax.dynamic_slice_in_dim(x, r * chunk, chunk, axis=axis)


def alltoall(x, axes: Sequence[str], split_axis: int, concat_axis: int, tiled: bool = True):
    if len(axes) != 1:
        raise NotImplementedError(
            "alltoall is defined over single-axis communicators "
            f"(got axes={tuple(axes)}); split the communicator"
        )
    return lax.all_to_all(x, axes[0], split_axis, concat_axis, tiled=tiled)


def scan_fold(x, fn: Callable, axes: Sequence[str], inclusive: bool = True):
    """Prefix reduction over linearized communicator rank (MPI_Scan/Exscan).

    Gathers every rank's contribution into a leading axis in linearized
    (row-major) rank order, then folds via the shared kernel
    (``emulation.prefix_fold`` — one definition of the exscan rank-0
    convention for native and emulated backends alike)."""
    axes = tuple(axes)
    if not axes:
        return x
    g = allgather(x[None], axes, axis=0)  # (S, *x.shape), linear rank order
    return prefix_fold(g, rank(axes), fn, x, inclusive)


def _alltoall_hier_uniform(x, axes: Sequence[str], c: int):
    """Hierarchical uniform-count all-to-all over a multi-axis communicator
    (row-major linearized rank/peer order), decomposed axis by axis the way
    ``ring_scan_sum_multi`` decomposes the prefix scan: route the major
    digit of every destination over the major axis first, transpose the
    minor destination blocks to the front, recurse over the remaining axes,
    and transpose back into source-major order.  ``len(axes)`` single-axis
    ``all_to_all`` phases move the same bytes a flat S-peer exchange would,
    but each phase stays inside one mesh axis — the 2D-torus schedule.

    ``x``: ``(S*c, ...)`` rows grouped by linearized destination; returns
    the same shape grouped by linearized source."""
    a0 = axes[0]
    A = lax.axis_size(a0)
    tail = x.shape[1:]
    if len(axes) == 1:
        return alltoall(x, (a0,), 0, 0)
    import math

    R = math.prod(lax.axis_size(a) for a in axes[1:])
    # phase 1: deliver each destination's major digit over the major axis
    # (A blocks of R*c rows); block a0 is then the data *from* major-source
    # a0, still ordered by minor destination
    y = alltoall(x, (a0,), 0, 0)
    y = y.reshape((A, R, c) + tail)
    # group by minor destination and recurse (blocks of A*c rows)
    y = jnp.swapaxes(y, 0, 1).reshape((R * A * c,) + tail)
    y = _alltoall_hier_uniform(y, axes[1:], A * c)
    # rows are now (minor-source, major-source); back to row-major source
    y = y.reshape((R, A, c) + tail)
    return jnp.swapaxes(y, 0, 1).reshape((A * R * c,) + tail)


def alltoallv(x, sendcounts: Sequence[int], recvcounts: Sequence[int],
              axes: Sequence[str]):
    """Counted all-to-all over the leading array axis (MPI_Alltoallv).

    ``x`` holds ``sum(sendcounts)`` rows: block *i* (``sendcounts[i]`` rows)
    goes to peer *i*; ``recvcounts[j]`` rows come back from peer *j*, in
    peer order.  Multi-axis communicators decompose hierarchically
    (:func:`_alltoall_hier_uniform`); peers are linearized row-major, so
    the result is indistinguishable from a flat single-axis exchange.

    **SPMD restriction:** a single static trace shares one counts vector
    across every rank, so per-rank-varying counts are not representable —
    rank *j* would be sending ``sendcounts[i]`` rows toward rank *i* while
    rank *i* slices ``recvcounts[j]``, and the two only agree when all
    counts are equal.  Non-uniform counts therefore raise ``ValueError``
    instead of silently fabricating padding or dropping rows."""
    axes = tuple(axes)
    sendcounts = tuple(int(c) for c in sendcounts)
    recvcounts = tuple(int(c) for c in recvcounts)
    if len(sendcounts) != len(recvcounts):
        raise ValueError("sendcounts and recvcounts must have equal length")
    uniform = set(sendcounts) | set(recvcounts)
    if len(uniform) != 1:
        raise ValueError(
            "SPMD alltoallv requires uniform counts (one static trace cannot "
            f"express per-rank-varying counts); got sendcounts={sendcounts}, "
            f"recvcounts={recvcounts}"
        )
    c = sendcounts[0]
    S = len(sendcounts)
    if x.shape[0] != S * c:
        raise ValueError(
            f"payload has {x.shape[0]} rows, counts promise {S}x{c}"
        )
    if not axes:
        # group of one: the only peer is self
        if S != 1:
            raise ValueError("group-of-one alltoallv takes exactly one count")
        return x
    if c == 0:
        return x[:0]
    if len(axes) > 1:
        return _alltoall_hier_uniform(x, tuple(axes), c)
    out = alltoall(x.reshape((S, c) + x.shape[1:]), axes, 0, 0)
    return out.reshape((S * c,) + x.shape[1:])


def ppermute(x, axes: Sequence[str], perm):
    if not axes:  # group of one: the only legal perm is the identity
        return x
    if len(axes) != 1:
        raise NotImplementedError("point-to-point permutation needs a single-axis comm")
    return lax.ppermute(x, axes[0], perm)


def bcast(x, root: int, axes: Sequence[str]):
    """Broadcast from linearized rank ``root`` via masked psum (one
    all-reduce; avoids materializing a full all-gather)."""
    if not axes:
        return x
    r = rank(axes)
    mask = (r == root).astype(x.dtype)
    return lax.psum(x * mask, tuple(axes)) if jnp.issubdtype(x.dtype, jnp.floating) else lax.psum(
        jnp.where(r == root, x, jnp.zeros_like(x)), tuple(axes)
    )


def barrier(axes: Sequence[str]):
    """Synchronization point: a zero-payload all-reduce the scheduler cannot
    elide (optimization_barrier on both sides)."""
    if not axes:
        return None
    t = jnp.zeros((), dtype=jnp.float32)
    (t,) = lax.optimization_barrier((t,))
    t = lax.psum(t, tuple(axes))
    (t,) = lax.optimization_barrier((t,))
    return t


def scatter_from_root(x, root: int, axes: Sequence[str], axis: int = 0):
    """SPMD scatter: input replicated (or defined on root); each device takes
    its chunk. With root!=self the payload still moves via the bcast."""
    x = bcast(x, root, axes)
    r = rank(axes)
    import math

    total = math.prod(lax.axis_size(a) for a in axes) if axes else 1
    chunk = x.shape[axis] // total
    return lax.dynamic_slice_in_dim(x, r * chunk, chunk, axis=axis)
