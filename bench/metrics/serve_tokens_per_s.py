"""Output tokens emitted in the window over the window's seconds."""
from . import _serve


def read(run):
    return _serve.tokens_in_window(run) / run.seconds
