"""Model step: the prefill program's share of the chip's bf16 peak, the
operations a chunk needs (its real positions' matrix products, causal
attention at each position's context, the head once) over the program's
mean device time a call in the traced window.  Reads the
``pax.serve.prefill`` span's arguments; a program without them reads
nothing."""
from . import _span_args as A


def read(run):
    return A.share(run, A.PREFILL, ("start", "real"), "bench.mark.prefill",
                   A.prefill_flops, "bf16_flops_per_s")
