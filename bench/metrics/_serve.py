"""Request-level numbers of a serving run, shared by its metric readers.

A request is due at ``due``; its tokens are stamped when the engine step
that made them returned.  Requests due in the lead-in before the window
are load: their tokens and gaps inside the window count, their first
tokens do not.  Only what happened by the window's close counts: a
request still waiting for its first token, or for its next one, when the
window closes counts its wait up to the close, so a stall at the end is
never hidden.
"""
from __future__ import annotations

import numpy as np


def _end(run) -> float:
    return run.window[1]


def due_in_window(run) -> list:
    return [r for r in run.records["requests"] if r.due >= run.window[0]]


def ttfts(run) -> list[float]:
    end = _end(run)
    out = []
    for r in due_in_window(run):
        first = r.tokens[0] if r.tokens and r.tokens[0] <= end else end
        out.append(first - r.due)
    return out


def token_gaps(run) -> list[float]:
    """Gaps whose later token came in the window, and for each request
    still decoding at the close the wait since its last token."""
    t0, end = run.window
    out = []
    for r in run.records["requests"]:
        ts = np.asarray([t for t in r.tokens if t <= end])
        if len(ts) > 1:
            later = ts[1:]
            out.extend((later - ts[:-1])[later >= t0].tolist())
        if len(ts) and not (r.req.done and len(ts) == len(r.tokens)):
            out.append(end - ts[-1])
    return out


def tokens_in_window(run) -> int:
    t0, end = run.window
    return sum(sum(t0 <= t <= end for t in r.tokens)
               for r in run.records["requests"])


def queue_waits(run) -> list[float]:
    end = _end(run)
    return [(r.admit if r.admit is not None and r.admit <= end else end) - r.due
            for r in due_in_window(run)]


def p95_ms(xs) -> float | None:
    return float(np.percentile(xs, 95)) * 1e3 if len(xs) else None
