"""Model step: the decode program's share of the chip's HBM bandwidth,
the bytes a step must read (the matrix products' weights in bf16 and the
decoding rows' K and V at their context) over the program's mean device
time a call in the traced window.  Reads the ``pax.serve.decode`` span's
arguments; a program without them reads nothing."""
from . import _span_args as A


def read(run):
    return A.share(run, A.DECODE, ("context",), "bench.mark.decode",
                   A.decode_bytes, "hbm_bytes_per_s")
