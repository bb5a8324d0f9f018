"""Fused ring-wire Pallas kernels: one HBM round trip per hop.

The ring backend's compressed wire (``core/backends/ring.py``) composes each
hop from separate lax ops — dequantize the received block, add the local
chunk, re-quantize for the next hop — which materializes three full-size
intermediates per hop.  Each kernel here does the whole per-hop update in a
single pass: one read of the traveling block, one read of the local chunk,
one write of the outgoing block (plus the tiny per-block scale vector).

Layout convention: every payload is viewed as ``(nblocks, WIRE_BLOCK)`` —
the wire block is the quantization granule (int8 absmax scale per block,
an upgrade over the lax path's single global scale) and the lane dimension
of the TPU tile.  The ops wrappers (:mod:`.ops`) own the reshape.  Every
kernel runs on a grid of row tiles of at most :data:`ROW_TILE` rows (the
scale vectors tile as ``(rows, 1)``), so the VMEM a kernel needs is fixed
by the tile, never by the payload: any wire size compiles.  Rows are
independent (one scale per row), so a partial last tile only computes
rows whose results the kernel drops.  ``interpret=True`` runs the same
kernels as jnp ops on CPU (the test/CI story).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import WIRE_BLOCK

#: rows per grid step: a (1024, 128) f32 block is 512 KiB, so the largest
#: kernel (int8 middle hop, double-buffered, scales padded to full lanes in
#: VMEM) stays near 4 MiB — well inside the 16 MiB default scoped VMEM.
#: A multiple of 32, the int8 sublane tile.
ROW_TILE = 1024

#: absmax floor matching ``ring._quantize`` (avoids 0/0 on all-zero blocks)
_QEPS = 1e-30

#: scale = absmax * (1/127) as a single f32 multiply — a divide here is
#: lowered differently inside vs outside the fused kernel body (1-ULP
#: drift), which would break the bitwise kernel==ref parity contract
_INV127 = float(jnp.float32(1.0) / jnp.float32(127.0))


def _i8_scales(x):
    """Per-block int8 absmax scale of a (nb, WIRE_BLOCK) f32 view."""
    return jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                       _QEPS) * _INV127


def _i8_pack(x, s):
    return jnp.clip(jnp.round(x / s), -127.0, 127.0).astype(jnp.int8)


def _tile(rows: int) -> int:
    """Row-tile height: the whole axis when it fits one tile (a block equal
    to the array dim is always legal), else :data:`ROW_TILE`."""
    return rows if rows <= ROW_TILE else ROW_TILE


def _row_tiled(kernel, out_shape, *args, interpret: bool):
    """``pallas_call`` over row tiles of 2-D operands sharing a leading
    row axis: payloads are ``(nb, WIRE_BLOCK)``, scales ``(nb, 1)``."""
    nb = args[0].shape[0]
    t = _tile(nb)

    def spec(a):
        return pl.BlockSpec((t, a.shape[1]), lambda i: (i, 0))

    multi = isinstance(out_shape, tuple)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(pl.cdiv(nb, t),),
        in_specs=[spec(a) for a in args],
        out_specs=(tuple(spec(o) for o in out_shape) if multi
                   else spec(out_shape)),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# int8 wire: quantize / hop-update / final-accumulate
# ---------------------------------------------------------------------------
def _quant_i8_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]
    s = _i8_scales(x)
    q_ref[...] = _i8_pack(x, s)
    s_ref[...] = s


def quant_i8(x2d, *, interpret: bool):
    """(nb, B) f32 -> ((nb, B) int8, (nb, 1) f32 scales)."""
    nb, b = x2d.shape
    return _row_tiled(
        _quant_i8_kernel,
        (jax.ShapeDtypeStruct((nb, b), jnp.int8),
         jax.ShapeDtypeStruct((nb, 1), jnp.float32)),
        x2d, interpret=interpret)


def _hop_add_quant_i8_kernel(q_ref, s_ref, a_ref, q2_ref, s2_ref):
    # dequantize + accumulate + re-quantize: ONE read of the traveling
    # block, one write of the outgoing block — the lax composition
    # materializes `received`, `travel` and the quantized result separately
    y = q_ref[...].astype(jnp.float32) * s_ref[...] + a_ref[...]
    s2 = _i8_scales(y)
    q2_ref[...] = _i8_pack(y, s2)
    s2_ref[...] = s2


def hop_add_quant_i8(q2d, s, a2d, *, interpret: bool):
    """Middle ring hop: (q, scales, local chunk) -> (q', scales')."""
    nb, b = q2d.shape
    return _row_tiled(
        _hop_add_quant_i8_kernel,
        (jax.ShapeDtypeStruct((nb, b), jnp.int8),
         jax.ShapeDtypeStruct((nb, 1), jnp.float32)),
        q2d, s, a2d, interpret=interpret)


def _hop_accum_i8_kernel(q_ref, s_ref, a_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...] + a_ref[...]


def hop_accum_i8(q2d, s, a2d, *, interpret: bool):
    """Final ring hop: dequantize-and-accumulate into f32, one pass."""
    return _row_tiled(
        _hop_accum_i8_kernel,
        jax.ShapeDtypeStruct(q2d.shape, jnp.float32),
        q2d, s, a2d, interpret=interpret)


# ---------------------------------------------------------------------------
# bf16 wire: pack is a bare cast (bitwise == lax astype); the fused work is
# the add+cast hop update and the final accumulate
# ---------------------------------------------------------------------------
def _hop_add_quant_bf16_kernel(w_ref, a_ref, w2_ref):
    w2_ref[...] = (w_ref[...].astype(jnp.float32) + a_ref[...]).astype(jnp.bfloat16)


def hop_add_quant_bf16(w2d, a2d, *, interpret: bool):
    return _row_tiled(
        _hop_add_quant_bf16_kernel,
        jax.ShapeDtypeStruct(w2d.shape, jnp.bfloat16),
        w2d, a2d, interpret=interpret)


def _hop_accum_bf16_kernel(w_ref, a_ref, o_ref):
    o_ref[...] = w_ref[...].astype(jnp.float32) + a_ref[...]


def hop_accum_bf16(w2d, a2d, *, interpret: bool):
    return _row_tiled(
        _hop_accum_bf16_kernel,
        jax.ShapeDtypeStruct(w2d.shape, jnp.float32),
        w2d, a2d, interpret=interpret)


# ---------------------------------------------------------------------------
# fused grad flatten/bucket: the zero1 transposed-bucket gather
# (grad_sync._transposed_bucket_parts) as one kernel pass, optionally fused
# with the bf16 wire cast + error-feedback residual refresh.
#
# Layout: the rank-major flat vector is viewed as (dp*buckets, rows, lanes)
# — segment r = d*buckets + b is rank d's b-th sub-slice — and the wire as
# (buckets, dp, rows, lanes).  The grid walks (rank, bucket, row tile); the
# transpose lives entirely in the index maps, so each step is a plain
# tile copy (plus cast / ef arithmetic).
# ---------------------------------------------------------------------------
def _seg_spec(t, lanes, buckets):
    # rank-major segment (d, b) of the flat view
    return pl.BlockSpec((None, t, lanes),
                        lambda d, b, j: (d * buckets + b, j, 0))


def _wire_spec(t, lanes):
    # bucket-major slot (b, d) of the wire view
    return pl.BlockSpec((None, None, t, lanes), lambda d, b, j: (b, d, j, 0))


def _grid(dp, buckets, rows, t):
    return (dp, buckets, pl.cdiv(rows, t))


def _pack_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def pack_transposed(x3d, dp: int, buckets: int, wire_dtype, *, interpret: bool):
    """(dp*buckets, rows, lanes) -> (buckets, dp, rows, lanes) in the wire
    dtype."""
    _, rows, lanes = x3d.shape
    t = _tile(rows)
    return pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((buckets, dp, rows, lanes), wire_dtype),
        grid=_grid(dp, buckets, rows, t),
        in_specs=[_seg_spec(t, lanes, buckets)],
        out_specs=_wire_spec(t, lanes),
        interpret=interpret,
    )(x3d)


def _pack_ef_kernel(x_ref, e_ref, o_ref, ef_ref):
    # error-feedback fold + bf16 wire cast + residual refresh, one pass:
    # y = g + ef; wire = bf16(y); ef' = y - f32(wire).  The lax path
    # materializes y, wire and ef' as three full vectors.
    y = x_ref[...] + e_ref[...]
    w = y.astype(jnp.bfloat16)
    ef_ref[...] = y - w.astype(jnp.float32)
    o_ref[...] = w


def pack_transposed_ef(x3d, e3d, dp: int, buckets: int, *, interpret: bool):
    """((dp*buckets, rows, lanes) f32 grads, same-shape ef) ->
    ((buckets, dp, rows, lanes) bf16 wire, same-shape-as-x f32 new ef)."""
    _, rows, lanes = x3d.shape
    t = _tile(rows)
    seg = _seg_spec(t, lanes, buckets)
    return pl.pallas_call(
        _pack_ef_kernel,
        out_shape=(jax.ShapeDtypeStruct((buckets, dp, rows, lanes),
                                        jnp.bfloat16),
                   jax.ShapeDtypeStruct(x3d.shape, jnp.float32)),
        grid=_grid(dp, buckets, rows, t),
        in_specs=[seg, seg],
        out_specs=(_wire_spec(t, lanes), seg),
        interpret=interpret,
    )(x3d, e3d)


def _unpack_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.float32)


def unpack_transposed(x4d, *, interpret: bool):
    """(buckets, dp, rows, lanes) -> (dp*buckets, rows, lanes) f32 — the
    inverse gather (grad_sync._interleave_bucket_gathers)."""
    buckets, dp, rows, lanes = x4d.shape
    t = _tile(rows)
    return pl.pallas_call(
        _unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((dp * buckets, rows, lanes),
                                       jnp.float32),
        grid=_grid(dp, buckets, rows, t),
        in_specs=[_wire_spec(t, lanes)],
        out_specs=_seg_spec(t, lanes, buckets),
        interpret=interpret,
    )(x4d)
