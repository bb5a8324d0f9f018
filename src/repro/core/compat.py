"""Thin wrappers for the jax calls whose keyword defaults the ABI layer
fixes once: meshes with Auto axis types, ``shard_map`` with the
varying-manual-axes check off and an optional partial-manual axis set, and
:func:`host_shard_map` for ABI regions called from the host every step.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

from ..runtime import spans


def make_mesh(shape: Sequence[int], names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(names)),
    )


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Sequence[str]] = None,
              check_vma: bool = False):
    kwargs = {"check_vma": check_vma}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def host_shard_map(f, *, mesh, in_specs, out_specs):
    """A :func:`shard_map` called from the host that re-runs ``f``'s Python
    on every call and compiles only programs it has not run before.

    The ABI's per-call protocol (plan start/wait, tool interposition,
    fault injection, wait deadlines) is Python that must run on each call,
    so the region is traced anew each time.  jax's own eager ``shard_map``
    does that too, but it also compiles every primitive inside afresh on
    each call; here the traced program is lowered, and its executable is
    kept under the lowered text and reused whenever the trace comes out
    the same.  The callable counts its ``calls`` and ``compiles``, and
    each call's lowering, compile and run are ``pax.abi.region.*``
    spans."""
    region = shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    executables: dict[str, object] = {}

    def call(*args):
        # a fresh function per call: jit would otherwise reuse its trace
        # and skip the Python
        def abi_region(*a):
            return region(*a)

        call.calls += 1
        with spans.span(spans.REGION_LOWER):
            lowered = jax.jit(abi_region).lower(*args)
            key = lowered.as_text()
        exe = executables.get(key)
        if exe is None:
            call.compiles += 1
            with spans.span(spans.REGION_COMPILE):
                exe = executables[key] = lowered.compile()
        with spans.span(spans.REGION_RUN):
            return exe(*args)

    call.calls = 0       # regions run
    call.compiles = 0    # programs compiled (lowered text not seen before)
    return call
