"""Fused ring-wire Pallas kernels (see README.md)."""
from .ops import (  # noqa: F401
    WIRE_BLOCK,
    hop_accum,
    hop_add_quant,
    interpret_on,
    pack_eligible,
    pack_parts,
    pack_parts_ef,
    quant,
    unpack_gathers,
    wire_eligible,
)
