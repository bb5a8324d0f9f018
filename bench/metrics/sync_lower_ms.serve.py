"""Decode sync through the ABI: host time spent tracing and lowering the
host-called ABI region (the program's ``pax.abi.region.lower`` span), per
decode step in the traced window.  A program without the span reads
nothing."""
from . import _program


def read(run):
    return _program.ms_per_decode_step(run, "pax.abi.region.lower")
