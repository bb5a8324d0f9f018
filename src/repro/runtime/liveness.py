"""Heartbeat liveness: an *observed* failure detector built from the ABI.

PR 7's fault tier recovers from ``PAX_ERR_PROC_FAILED``, but until now the
failure itself was always *declared* — a ``faulty:`` schedule or a
hand-set ``local_failed`` view told the detector who died.  This module
closes that gap the way the MPICH extension papers prototype liveness: as
a **library walk over the existing surface**, no new ABI entries.

:class:`HeartbeatMonitor` piggybacks a periodic tick exchange over the
ABI's own ``sendrecv`` on a **dedicated duplicated communicator**
(``comm_dup``), so heartbeat traffic never contends with the workload's
plan groups and is never poisoned by a workload-comm revoke.  Each
:meth:`~HeartbeatMonitor.beat`:

* runs one ring ``sendrecv`` of the current tick over the heartbeat comm
  (a host-called ``shard_map`` region, as in a ``DecodeSync`` step);
* attributes non-responders through the transport's
  ``Backend.heartbeat_silent`` hook (a rank declared dead by a ``faulty:``
  schedule stops answering — the wrapper is now one *producer* of missed
  heartbeats, not the only failure source) plus any test-injected silence;
* advances a miss-threshold → suspicion → confirmation state machine:
  a rank silent for ``miss_threshold`` consecutive ticks becomes
  *suspected*; silent for ``suspicion_ticks`` more it is *confirmed*
  failed; answering while suspected clears the suspicion (a straggler is
  not a corpse).

:meth:`~HeartbeatMonitor.install` chains the monitor's confirmed view
onto the backend's ``local_failed`` **instance attribute** — the one
funnel both the native fault hooks and the emulation recipes read — so a
heartbeat-confirmed death surfaces through ``comm_get_failed`` /
``comm_agree`` exactly like a declared one, and the standard
revoke → ack → agree → shrink walk recovers from it.  After the shrink,
:meth:`~HeartbeatMonitor.rebind` re-dups the heartbeat comm onto the
survivor communicator (confirmed corpses stay confirmed; they are
non-members of the survivor comm and filter out of its view).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..core.errors import PAX_ERR_PROC_FAILED, PaxError


class HeartbeatMonitor:
    """Miss-threshold failure detector over a duplicated heartbeat comm.

    ``miss_threshold`` consecutive missed ticks raise suspicion;
    ``suspicion_ticks`` total silent ticks in the suspected state (the
    suspicion tick included) confirm the death.  A rank is therefore
    confirmed after exactly ``miss_threshold + suspicion_ticks - 1``
    consecutive silent ticks — the edge the unit tests pin.
    """

    def __init__(self, abi, comm, mesh, *, miss_threshold: int = 3,
                 suspicion_ticks: int = 2) -> None:
        if miss_threshold < 1:
            raise ValueError(f"miss_threshold must be >= 1, got {miss_threshold}")
        if suspicion_ticks < 1:
            raise ValueError(f"suspicion_ticks must be >= 1, got {suspicion_ticks}")
        self.abi = abi
        self.comm = comm
        self.mesh = mesh
        self.miss_threshold = miss_threshold
        self.suspicion_ticks = suspicion_ticks
        self.tick = 0
        self.last_seen: dict[int, int] = {}
        self.suspected: dict[int, int] = {}   # rank -> tick suspicion began
        self.confirmed: set[int] = set()
        self._injected: set[int] = set()
        self._installed: Optional[tuple] = None
        # heartbeats ride their own duplicated comm: never revoked by the
        # workload walk, never sharing the workload's plan slots
        self.hb_comm = abi.comm_dup(comm)
        self._build_exchange()

    # -- membership ---------------------------------------------------------
    def members(self) -> list[int]:
        info = self.abi.comms.info(self.comm, allow_revoked=True)
        return [r for r in range(info.full_size) if r not in info.excludes]

    def _build_exchange(self) -> None:
        from jax.sharding import PartitionSpec as P

        from ..core.compat import host_shard_map

        abi, hb = self.abi, self.hb_comm
        members = self.members()
        # ring over the members in full-rank space (excludes skipped): every
        # member sends its tick to the next and hears from the previous —
        # one silent rank starves exactly its ring neighbour's receive
        perm = [(members[i], members[(i + 1) % len(members)])
                for i in range(len(members))]

        def _beat(x):
            return abi.sendrecv(x, perm, hb)

        self._exchange = host_shard_map(_beat, mesh=self.mesh,
                                        in_specs=P(), out_specs=P())

    # -- test hooks ---------------------------------------------------------
    def inject_silence(self, rank: int) -> None:
        """Make ``rank`` stop answering (test hook; the ``faulty:`` wrapper
        injects the same way through ``heartbeat_silent``)."""
        self._injected.add(rank)

    def clear_silence(self, rank: int) -> None:
        self._injected.discard(rank)

    def _silent_now(self) -> set[int]:
        silent = set(self._injected)
        fn = getattr(self.abi.backend, "heartbeat_silent", None)
        if fn is not None:
            silent.update(fn(self.hb_comm))
        return silent

    # -- the beat -----------------------------------------------------------
    def beat(self) -> tuple:
        """One heartbeat round; returns the currently-confirmed failures.

        The tick exchange's ``PAX_ERR_PROC_FAILED`` is absorbed here (a
        failed heartbeat is an *observation*, not an error); ``REVOKED``
        and every other error propagate — the heartbeat comm is ours and
        nothing should be revoking it.
        """
        self.tick += 1
        exchanged = True
        try:
            self._exchange(jnp.full((1,), self.tick, jnp.int32))
        except PaxError as e:
            if e.code != PAX_ERR_PROC_FAILED:
                raise
            exchanged = False
        silent = self._silent_now()
        members = self.members()
        if exchanged or silent:
            responders = {r for r in members if r not in silent}
        else:
            # the exchange died with no transport attribution: trust nobody
            # this tick (conservative — everyone's miss counter advances)
            responders = set()
        for r in members:
            if r in responders:
                self.last_seen[r] = self.tick
                self.suspected.pop(r, None)
                continue
            if r in self.confirmed:
                continue
            misses = self.tick - self.last_seen.get(r, 0)
            if r not in self.suspected and misses >= self.miss_threshold:
                self.suspected[r] = self.tick
            began = self.suspected.get(r)
            if began is not None and self.tick - began + 1 >= self.suspicion_ticks:
                self.suspected.pop(r)
                self.confirmed.add(r)
        return self.failed(self.comm)

    # -- the detector view --------------------------------------------------
    def failed(self, comm) -> tuple:
        """Confirmed failures that are members of ``comm`` — the shape of
        ``Backend.local_failed``, which :meth:`install` chains onto."""
        try:
            info = self.abi.comms.info(comm, allow_revoked=True)
        except PaxError:
            return ()
        if not info.axes:
            return ()
        return tuple(r for r in sorted(self.confirmed)
                     if r not in info.excludes and r < info.full_size)

    def install(self) -> "HeartbeatMonitor":
        """Chain the monitor onto the backend's ``local_failed`` funnel.

        Set as an *instance attribute* on the backend, so the native fault
        hooks (rebound class functions reading ``self.local_failed``), the
        emulation recipes (``EmulationContext.local_failed``) and the
        Mukautuva adapter all observe the union of the transport's own
        view and the monitor's confirmed deaths.
        """
        if self._installed is not None:
            return self
        backend = self.abi.backend
        inner = backend.local_failed
        monitor = self

        def local_failed(comm):
            seen = tuple(inner(comm))
            return seen + tuple(r for r in monitor.failed(comm)
                                if r not in seen)

        backend.local_failed = local_failed
        self._installed = (backend, inner)
        return self

    def uninstall(self) -> None:
        if self._installed is None:
            return
        backend, inner = self._installed
        backend.local_failed = inner
        self._installed = None

    # -- recovery -----------------------------------------------------------
    def rebind(self, survivor_comm) -> None:
        """Move the heartbeat onto the post-shrink survivor communicator.

        Confirmed corpses stay confirmed (they are non-members of the
        survivor comm, so :meth:`failed` filters them from its view);
        suspicion and miss counters reset — the survivors just proved
        themselves live by completing the shrink agreement.
        """
        self.comm = survivor_comm
        self.hb_comm = self.abi.comm_dup(survivor_comm)
        self._build_exchange()
        self.suspected.clear()
        for r in self.members():
            self.last_seen[r] = self.tick
