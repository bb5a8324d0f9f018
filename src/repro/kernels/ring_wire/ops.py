"""Shape-polymorphic wrappers around the fused ring-wire kernels.

These are the functions the backend plan hooks call.  Payloads arrive as
flat (or leading-axis) arrays; the wrappers view them as ``(nblocks,
WIRE_BLOCK)``, invoke the row-tiled kernel, and restore the caller's shape.
Eligibility predicates (:func:`wire_eligible`, :func:`pack_eligible`) are
evaluated at **plan time** against the bound shape/dtype/platform — callers
never see the kernel-vs-lax decision, only ``capabilities()`` does.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from . import kernel as _k

WIRE_BLOCK = _k.WIRE_BLOCK


def _platform(platform: Optional[str]) -> str:
    return platform or jax.default_backend()


def interpret_on(platform: Optional[str] = None) -> bool:
    """Pallas interpret mode: on for CPU (tests/CI), off on TPU/GPU."""
    return _platform(platform) == "cpu"


def wire_eligible(shape, dtype, compress: Optional[str],
                  platform: Optional[str] = None) -> bool:
    """Can the fused hop kernels carry this per-hop chunk?

    Requires a compressed wire (the fusion exists to kill the quantize /
    dequantize intermediates), an f32 payload, and a WIRE_BLOCK-divisible
    element count (the per-block scale layout).  Size is no condition: the
    kernels tile rows, so their VMEM need is fixed by the tile.
    """
    if compress not in ("int8", "bf16"):
        return False
    if jnp.dtype(dtype) != jnp.float32:
        return False
    total = 1
    for d in shape:
        total *= int(d)
    if total <= 0 or total % WIRE_BLOCK != 0:
        return False
    return _platform(platform) in ("cpu", "tpu", "gpu")


def _as_blocks(x):
    return x.reshape(-1, WIRE_BLOCK)


def _call(kernel, *arrays, **static):
    """``kernel(*arrays, **static)``.  The compiler cannot partition a
    Mosaic kernel, so where the caller traces inside a mesh whose axes are
    not all manual (the train step's region is manual over the dp axes
    only), the call runs under a ``shard_map`` over the remaining axes with
    every operand replicated: each device runs the kernel on its copy."""
    fn = functools.partial(kernel, **static)
    mesh = jax.sharding.get_abstract_mesh()
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual}
    if not auto:
        return fn(*arrays)
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * len(arrays),
                         out_specs=P(), axis_names=auto,
                         check_vma=False)(*arrays)


def quant(x, compress: str, *, interpret: bool):
    """Quantize a chunk for the wire.

    Returns ``(q, scales)`` where ``q`` has ``x``'s shape (int8 or bf16)
    and ``scales`` is the per-block scale vector (``None`` for bf16).
    """
    if compress == "bf16":
        # bare cast: bitwise-identical to the lax astype, no kernel needed
        return x.astype(jnp.bfloat16), None
    q, s = _call(_k.quant_i8, _as_blocks(x), interpret=interpret)
    return q.reshape(x.shape), s


def hop_add_quant(q, scales, addend, compress: str, *, interpret: bool):
    """Middle-hop update: dequantize + add local chunk + re-quantize."""
    if compress == "bf16":
        w2 = _call(_k.hop_add_quant_bf16, _as_blocks(q), _as_blocks(addend),
                   interpret=interpret)
        return w2.reshape(q.shape), None
    q2, s2 = _call(_k.hop_add_quant_i8, _as_blocks(q), scales,
                   _as_blocks(addend), interpret=interpret)
    return q2.reshape(q.shape), s2


def hop_accum(q, scales, addend, compress: str, *, interpret: bool):
    """Final-hop update: dequantize + add local chunk, f32 out."""
    if compress == "bf16":
        o = _call(_k.hop_accum_bf16, _as_blocks(q), _as_blocks(addend),
                  interpret=interpret)
    else:
        o = _call(_k.hop_accum_i8, _as_blocks(q), scales, _as_blocks(addend),
                  interpret=interpret)
    return o.reshape(addend.shape)


# ---------------------------------------------------------------------------
# fused grad flatten/bucket (zero1 plan-group payload gather)
# ---------------------------------------------------------------------------
def _seg_view(seg: int) -> tuple[int, int]:
    """(rows, lanes) view of one bucket segment: WIRE_BLOCK lanes when the
    segment divides into them, else one short row."""
    if seg % WIRE_BLOCK == 0:
        return seg // WIRE_BLOCK, WIRE_BLOCK
    return 1, seg


def pack_eligible(padded: int, dp: int, buckets: int,
                  platform: Optional[str] = None) -> bool:
    """Can the fused pack/unpack kernels build the zero1 bucket parts?

    Each rank's bucket segment (``padded / (dp * buckets)`` elements) must
    tile as rows of WIRE_BLOCK lanes, or be shorter than one row (a single
    short row is one tile).  The ZeRO-1 flat layout pads to satisfy this
    (``adamw.zero1_padded_size``)."""
    if padded <= 0 or dp <= 0 or buckets <= 0 or padded % (dp * buckets) != 0:
        return False
    seg = padded // (dp * buckets)
    if seg % WIRE_BLOCK != 0 and seg >= WIRE_BLOCK:
        return False
    return _platform(platform) in ("cpu", "tpu", "gpu")


def pack_parts(flat, dp: int, buckets: int, wire_dtype, *, interpret: bool):
    """Fused ``_transposed_bucket_parts`` + wire cast.

    ``flat``: (padded,) f32 -> list of ``buckets`` parts, each
    ``(padded // buckets,)`` in ``wire_dtype``.
    """
    rows, lanes = _seg_view(flat.shape[0] // (dp * buckets))
    out = _call(_k.pack_transposed, flat.reshape(dp * buckets, rows, lanes),
                dp=dp, buckets=buckets, wire_dtype=jnp.dtype(wire_dtype),
                interpret=interpret)
    return [out[b].reshape(-1) for b in range(buckets)]


def pack_parts_ef(flat, ef, dp: int, buckets: int, *, interpret: bool):
    """Fused error-feedback fold + bf16 cast + residual + bucket gather.

    Returns ``(parts, new_ef)``: ``parts`` as in :func:`pack_parts` (bf16),
    ``new_ef`` the refreshed (padded,) f32 residual ``(g + ef) - f32(wire)``.
    """
    view = (dp * buckets,) + _seg_view(flat.shape[0] // (dp * buckets))
    out, new_ef = _call(_k.pack_transposed_ef, flat.reshape(view),
                        ef.reshape(view), dp=dp, buckets=buckets,
                        interpret=interpret)
    return [out[b].reshape(-1) for b in range(buckets)], new_ef.reshape(-1)


def unpack_gathers(outs, dp: int, *, interpret: bool):
    """Fused ``_interleave_bucket_gathers``: per-bucket allgather outputs
    (each ``(padded // buckets,)``) back to one (padded,) f32 vector."""
    rows, lanes = _seg_view(outs[0].shape[0] // dp)
    x4d = jnp.stack([o.reshape(dp, rows, lanes) for o in outs], axis=0)
    return _call(_k.unpack_transposed, x4d, interpret=interpret).reshape(-1)
