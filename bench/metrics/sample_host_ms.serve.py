"""Sampling: host time of sampling every decoding row (the program's
``pax.serve.sample`` span), per decode step in the traced window.  A
program without the span reads nothing."""
from . import _program


def read(run):
    return _program.ms_per_decode_step(run, "pax.serve.sample")
