"""Named host spans of the serving path, for the profiler.

Each span is a ``jax.profiler.TraceAnnotation`` with a fixed name; two
carry arguments (below), set only while a profiler records.  With no
profiler running, entering and leaving one costs about as much as
``contextlib.nullcontext``; while ``jax.profiler.start_trace``
(or a profiler server started by ``jax.profiler.start_server``) records,
the spans land in the trace's host plane, on the same clock as the
device's operations, so each idle stretch of the device can be put down
to what the host was doing.  Spans of one thread nest.  The arguments land
as the event's stats (``ProfileData`` event ``.stats``).

The catalog:

=========================== ===============================================
``pax.serve.step``          one ``ServeEngine.step`` (parent of the rest)
``pax.serve.admit``         deadline expiry and admission
``pax.serve.prefill``       one prefill chunk: build, dispatch, first token;
                            arguments ``start`` (the chunk's first
                            position), ``real`` (prompt positions in it)
                            and ``chunk`` (its width, pads included)
``pax.serve.decode``        one decode step, sampling and sync included;
                            arguments ``rows`` (decoding rows) and
                            ``context`` (positions they attend, summed:
                            each row's length + 1)
``pax.serve.decode.dispatch`` the step's host arrays and the jit call
``pax.serve.sample``        dispatching the row programs, one a decoding row,
                            and the stack of their tokens (one span a step,
                            before the wait)
``pax.serve.decode.wait``   waiting for the tokens: the decode program and
                            the row programs queued behind it
``pax.serve.decode.copy``   the ``(max_batch,)`` tokens to the host, one copy
``pax.serve.sync``          ``DecodeSync.step``: the ``decode-tp`` group
``pax.abi.region.lower``    a host-called ABI region traced and lowered
``pax.abi.region.compile``  the region compiled (first call of a program)
``pax.abi.region.run``      the region's executable called
``pax.host.gc``             one collection of Python's garbage collector
=========================== ===============================================
"""
from __future__ import annotations

import gc

import jax

span = jax.profiler.TraceAnnotation

SERVE_STEP = "pax.serve.step"
SERVE_ADMIT = "pax.serve.admit"
SERVE_PREFILL = "pax.serve.prefill"
SERVE_DECODE = "pax.serve.decode"
SERVE_DECODE_DISPATCH = "pax.serve.decode.dispatch"
SERVE_DECODE_WAIT = "pax.serve.decode.wait"
SERVE_DECODE_COPY = "pax.serve.decode.copy"
SERVE_SAMPLE = "pax.serve.sample"
SERVE_SYNC = "pax.serve.sync"
REGION_LOWER = "pax.abi.region.lower"
REGION_COMPILE = "pax.abi.region.compile"
REGION_RUN = "pax.abi.region.run"
HOST_GC = "pax.host.gc"

# the span of the collection in progress (collections never overlap)
_gc_open: list = [None]


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_open[0] = span(HOST_GC)
        _gc_open[0].__enter__()
    elif _gc_open[0] is not None:
        _gc_open[0].__exit__(None, None, None)
        _gc_open[0] = None


def install_gc_span() -> None:
    """Bracket every collection of the garbage collector in a
    ``pax.host.gc`` span (once per process, however often called)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
