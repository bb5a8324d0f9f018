"""95th percentile over every request due in the window of the time from
its due time to its first token (open loop)."""
from . import _serve


def read(run):
    return _serve.p95_ms(_serve.ttfts(run))
