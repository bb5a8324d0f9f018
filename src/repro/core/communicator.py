"""Communicators over JAX mesh axes.

An MPI communicator names an ordered process group.  In the JAX SPMD world
the processes are mesh devices, so a communicator resolves to an ordered
tuple of mesh axis names; collective calls made inside ``shard_map`` regions
lower over exactly those axes.

* ``PAX_COMM_WORLD`` → every axis of the active mesh (in mesh order);
* ``PAX_COMM_SELF``  → the empty axis tuple (group of one device);
* derived communicators (``comm_from_axes`` — the ``MPI_Comm_split``-shaped
  constructor) name axis subsets, e.g. the data-parallel group
  ``("pod", "data")`` or the expert-parallel group ``("model",)``.

Handles are the ABI ints from :mod:`handles`; per-context tables map them to
:class:`CommInfo`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
from jax import lax

from . import handles as H
from .errors import PAX_ERR_COMM, PAX_ERR_REVOKED, PaxError


@dataclasses.dataclass(frozen=True)
class CommInfo:
    handle: int
    axes: tuple[str, ...]  # ordered mesh axes; () == SELF
    mesh_axis_sizes: tuple[int, ...]
    name: str = ""
    #: ranks excluded from the group (ULFM shrink survivors-only comms).  The
    #: axes stay those of the parent — in the single-controller simulation a
    #: shrunk comm is the *transition artifact* carried from "revoked" to
    #: "training rebuilt a dense mesh over the survivors"; its job is to name
    #: the survivor group, not to run collectives inside the dead mesh.
    excludes: tuple[int, ...] = ()

    @property
    def full_size(self) -> int:
        """Group size before exclusions (the parent's extent)."""
        return math.prod(self.mesh_axis_sizes) if self.mesh_axis_sizes else 1

    @property
    def size(self) -> int:
        return self.full_size - len(self.excludes)


class CommTable:
    """Per-ABI-context communicator table."""

    def __init__(self, mesh: Optional[jax.sharding.Mesh]) -> None:
        self._mesh = mesh
        self._table: dict[int, CommInfo] = {}
        self._next_index = 0
        # registration-time-maintained flat lookup (handle -> axes) for the
        # per-call hot path: one dict index, no handle re-check, no CommInfo
        # attribute chase.  `info()` stays the checked metadata query.
        self.axes_by_handle: dict[int, tuple[str, ...]] = {}
        # -- fault tier state (ULFM) --------------------------------------
        # Revocation poisons the hot path by *construction*: `revoke()` pops
        # the handle from axes_by_handle, so the per-call fast lookup misses
        # and falls through to `info()`, which raises PAX_ERR_REVOKED.  The
        # unrevoked path stays byte-identical — no added check anywhere hot.
        self.revoked: set[int] = set()
        #: per-comm acknowledged failures (comm_failure_ack); agree refuses
        #: to proceed while unacknowledged failures exist (ULFM contract)
        self.acked: dict[int, frozenset] = {}
        axes = tuple(mesh.axis_names) if mesh is not None else ()
        sizes = tuple(mesh.shape[a] for a in axes) if mesh is not None else ()
        self._table[H.PAX_COMM_WORLD] = CommInfo(
            H.PAX_COMM_WORLD, axes, sizes, "PAX_COMM_WORLD"
        )
        self._table[H.PAX_COMM_SELF] = CommInfo(H.PAX_COMM_SELF, (), (), "PAX_COMM_SELF")
        self.axes_by_handle[H.PAX_COMM_WORLD] = axes
        self.axes_by_handle[H.PAX_COMM_SELF] = ()

    @property
    def mesh(self) -> Optional[jax.sharding.Mesh]:
        return self._mesh

    def info(self, handle: int, *, allow_revoked: bool = False) -> CommInfo:
        H.check_handle(handle, H.HandleKind.COMM)
        if handle == H.PAX_COMM_NULL:
            raise PaxError(PAX_ERR_COMM, "PAX_COMM_NULL")
        try:
            info = self._table[handle]
        except KeyError:
            raise PaxError(PAX_ERR_COMM, H.describe(handle)) from None
        if self.revoked and handle in self.revoked and not allow_revoked:
            # only the fault-tier entries (revoke/agree/shrink/ack/get_failed)
            # may operate on a revoked communicator — the ULFM contract
            raise PaxError(PAX_ERR_REVOKED, info.name or H.describe(handle))
        return info

    def comm_from_axes(self, axes: Sequence[str], name: str = "") -> int:
        """Create a communicator over a subset of mesh axes (split analogue)."""
        if self._mesh is None:
            raise PaxError(PAX_ERR_COMM, "no mesh bound to this context")
        axes = tuple(axes)
        for a in axes:
            if a not in self._mesh.axis_names:
                raise PaxError(PAX_ERR_COMM, f"axis {a!r} not in mesh {self._mesh.axis_names}")
        handle = H.make_user_handle(H.HandleKind.COMM, self._next_index)
        self._next_index += 1
        sizes = tuple(self._mesh.shape[a] for a in axes)
        self._table[handle] = CommInfo(handle, axes, sizes, name or f"axes{axes}")
        self.axes_by_handle[handle] = axes
        return handle

    def comm_dup(self, handle: int) -> int:
        info = self.info(handle)
        new = H.make_user_handle(H.HandleKind.COMM, self._next_index)
        self._next_index += 1
        self._table[new] = dataclasses.replace(info, handle=new, name=info.name + "+dup")
        self.axes_by_handle[new] = info.axes
        return new

    def comm_free(self, handle: int) -> None:
        if H.is_predefined(handle):
            raise PaxError(PAX_ERR_COMM, "cannot free a predefined communicator")
        self._table.pop(handle, None)
        self.axes_by_handle.pop(handle, None)
        self.revoked.discard(handle)
        self.acked.pop(handle, None)

    # -- fault tier (ULFM) --------------------------------------------------
    def revoke(self, handle: int) -> None:
        """Mark ``handle`` revoked.  Idempotent.

        Enforcement is by hot-path poisoning: the handle leaves
        ``axes_by_handle``, so every collective's registration-time fast
        lookup misses and lands in :meth:`info`, which raises
        ``PAX_ERR_REVOKED``.  Nothing is added to the unrevoked path.
        """
        self.info(handle, allow_revoked=True)  # validate the handle
        self.revoked.add(handle)
        self.axes_by_handle.pop(handle, None)

    def is_revoked(self, handle: int) -> bool:
        return handle in self.revoked

    def register_shrunk(self, parent: int, excludes, name: str = "") -> int:
        """Register the dense survivor communicator of an ULFM shrink.

        The child carries the parent's axes with ``excludes`` recorded, so
        ``size`` reports the survivor count.  The child is *not* revoked
        even when the parent is — that is the entire point of shrink.
        """
        info = self.info(parent, allow_revoked=True)
        handle = H.make_user_handle(H.HandleKind.COMM, self._next_index)
        self._next_index += 1
        self._table[handle] = CommInfo(
            handle, info.axes, info.mesh_axis_sizes,
            name or (info.name + "+shrink"),
            excludes=tuple(sorted(set(info.excludes) | set(excludes))),
        )
        self.axes_by_handle[handle] = info.axes
        return handle


def comm_rank_traced(info: CommInfo):
    """Linearized rank within the communicator (row-major over its axes).

    Only valid inside a shard_map region where the axes are bound manual.
    """
    if not info.axes:
        return 0
    rank = lax.axis_index(info.axes[0])
    for a in info.axes[1:]:
        rank = rank * lax.axis_size(a) + lax.axis_index(a)
    return rank


def comm_size_static(info: CommInfo) -> int:
    return info.size
