"""Sampling: host time of copying the decode step's logits to the host
(the program's ``pax.serve.decode.copy`` span), per decode step in the
traced window.  A program without the span reads nothing."""
from . import _program


def read(run):
    return _program.ms_per_decode_step(run, "pax.serve.decode.copy")
