"""95th percentile of every gap between consecutive output tokens of every
request in the window, pooled."""
from . import _serve


def read(run):
    return _serve.p95_ms(_serve.token_gaps(run))
