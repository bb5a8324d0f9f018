"""Fused ring-wire kernels: interpret-mode parity against the lax oracles.

Contract (see kernels/ring_wire/ref.py):

* int8 quantize, both bf16 hop paths and the pack/unpack gather kernels are
  **bitwise** equal to the unfused lax composition of the same math;
* the int8 hop paths match to one quantum — inside the fused body the
  dequant+add contracts to an FMA (single rounding), which the unfused
  composition cannot express.  That is a property of real fused kernels,
  not an interpret-mode artifact, so the tests encode it rather than
  papering over it with loose tolerances.

Plus the plan-time selection surface (kernel registry, capability tags,
eligibility predicates), the hlo_analysis traffic breakdown that proves
the fusion claim, and the flash-attention registry routing.  Multi-device
behaviour (the fused hops inside a real ring schedule, grad_sync plans at
dp=2/8) lives in multidev_battery.py sections 9/10/12; compiles for the
chip at real widths live in test_tpu_compile.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.ring_wire import ops, ref
from repro.kernels.ring_wire.kernel import WIRE_BLOCK

KEY = jax.random.PRNGKey(7)
N = 8 * WIRE_BLOCK  # 8 scale blocks


def _vec(key, n=N, scale=3.0):
    return scale * jax.random.normal(key, (n,), jnp.float32)


# ---------------------------------------------------------------------------
# int8 hop kernels vs per-block oracles
# ---------------------------------------------------------------------------
def test_quant_i8_bitwise():
    x = _vec(KEY)
    q, s = ops.quant(x, "int8", interpret=True)
    qr, sr = ref.quant_i8_block(x)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_hop_add_quant_i8_one_quantum():
    k1, k2 = jax.random.split(KEY)
    x, a = _vec(k1), _vec(k2)
    q, s = ops.quant(x, "int8", interpret=True)
    q2, s2 = ops.hop_add_quant(q, s, a, "int8", interpret=True)
    q2r, s2r = ref.hop_add_quant_i8_block(q, s, a)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s2r), rtol=1e-6)
    diff = np.abs(np.asarray(q2, np.int32) - np.asarray(q2r, np.int32))
    assert diff.max() <= 1, f"int8 hop drifted {diff.max()} quanta"


def test_hop_accum_i8_close():
    k1, k2 = jax.random.split(KEY, 2)
    x, a = _vec(k1), _vec(k2)
    q, s = ops.quant(x, "int8", interpret=True)
    out = ops.hop_accum(q, s, a, "int8", interpret=True)
    outr = ref.hop_accum_i8_block(q, s, a)
    assert out.dtype == jnp.float32
    # FMA vs mul-then-add: within one rounding of the largest block scale
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr),
                               atol=float(jnp.max(s)))


def test_int8_end_to_end_error_bounded():
    """Dequantized hop result stays within quantization error of exact f32
    (per-block scales: error <= scale/2 per step, two quantization steps)."""
    k1, k2 = jax.random.split(KEY)
    x, a = _vec(k1), _vec(k2)
    q, s = ops.quant(x, "int8", interpret=True)
    q2, s2 = ops.hop_add_quant(q, s, a, "int8", interpret=True)
    approx = ref.dequant_i8_block(q2, s2)
    exact = x + a
    bound = float(jnp.max(s)) / 2 + float(jnp.max(s2)) / 2 + 1e-6
    assert np.abs(np.asarray(approx - exact)).max() <= bound


# ---------------------------------------------------------------------------
# bf16 hop kernels: bitwise vs the astype composition
# ---------------------------------------------------------------------------
def test_hop_bf16_bitwise():
    k1, k2 = jax.random.split(KEY)
    x, a = _vec(k1), _vec(k2)
    w, none = ops.quant(x, "bf16", interpret=True)
    assert none is None and w.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(w), np.asarray(x.astype(jnp.bfloat16)))

    w2, _ = ops.hop_add_quant(w, None, a, "bf16", interpret=True)
    w2r = (w.astype(jnp.float32) + a).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w2r))

    o = ops.hop_accum(w, None, a, "bf16", interpret=True)
    np.testing.assert_array_equal(np.asarray(o),
                                  np.asarray(w.astype(jnp.float32) + a))


# ---------------------------------------------------------------------------
# fused pack/unpack vs the grad_sync bucket helpers (bitwise)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dp,buckets,wire_dtype",
                         [(2, 1, jnp.float32), (2, 2, jnp.float32),
                          (4, 2, jnp.bfloat16), (8, 4, jnp.bfloat16)])
def test_pack_parts_matches_transposed_bucket_parts(dp, buckets, wire_dtype):
    from repro.train.grad_sync import _transposed_bucket_parts

    padded = dp * buckets * 12
    flat = _vec(KEY, padded)
    parts = ops.pack_parts(flat, dp, buckets, wire_dtype, interpret=True)
    refs = _transposed_bucket_parts(flat.astype(wire_dtype), dp, buckets)
    assert len(parts) == buckets
    for p, r in zip(parts, refs):
        assert p.dtype == jnp.dtype(wire_dtype)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(r))


def test_pack_parts_ef_matches_unfused_fold():
    from repro.train.grad_sync import _transposed_bucket_parts

    dp, buckets, padded = 4, 2, 4 * 2 * 24
    k1, k2 = jax.random.split(KEY)
    g, ef = _vec(k1, padded), 0.01 * _vec(k2, padded)
    parts, new_ef = ops.pack_parts_ef(g, ef, dp, buckets, interpret=True)
    y = g + ef
    wire = y.astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(new_ef), np.asarray(y - wire.astype(jnp.float32)))
    for p, r in zip(parts, _transposed_bucket_parts(wire, dp, buckets)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(r))


def test_unpack_gathers_inverts_pack():
    from repro.train.grad_sync import _interleave_bucket_gathers

    dp, buckets, padded = 4, 4, 4 * 4 * 16
    flat = _vec(KEY, padded)
    parts = ops.pack_parts(flat, dp, buckets, jnp.float32, interpret=True)
    # kernel inverse == helper inverse == identity
    back = ops.unpack_gathers(parts, dp, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(flat))
    np.testing.assert_array_equal(
        np.asarray(_interleave_bucket_gathers(parts, dp)), np.asarray(flat))


def test_multi_tile_grid_parity():
    """Payloads longer than one row tile (with a partial last tile) keep
    the single-tile contracts: bitwise quantize / bf16 hop / pack+unpack,
    one quantum on the int8 hop."""
    from repro.kernels.ring_wire.kernel import ROW_TILE
    from repro.train.grad_sync import _transposed_bucket_parts

    n = (ROW_TILE + 40) * WIRE_BLOCK
    k1, k2 = jax.random.split(KEY)
    x, a = _vec(k1, n), _vec(k2, n)
    q, s = ops.quant(x, "int8", interpret=True)
    qr, sr = ref.quant_i8_block(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    q2, _ = ops.hop_add_quant(q, s, a, "int8", interpret=True)
    q2r, _ = ref.hop_add_quant_i8_block(q, s, a)
    assert np.abs(np.asarray(q2, np.int32) - np.asarray(q2r, np.int32)).max() <= 1
    w = x.astype(jnp.bfloat16)
    o = ops.hop_accum(w, None, a, "bf16", interpret=True)
    np.testing.assert_array_equal(np.asarray(o),
                                  np.asarray(w.astype(jnp.float32) + a))

    dp, buckets = 2, 2
    flat = _vec(k1, dp * buckets * n)   # segments of ROW_TILE + 40 rows
    parts = ops.pack_parts(flat, dp, buckets, jnp.bfloat16, interpret=True)
    for p, r in zip(parts, _transposed_bucket_parts(
            flat.astype(jnp.bfloat16), dp, buckets)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(r))
    f32_parts = ops.pack_parts(flat, dp, buckets, jnp.float32, interpret=True)
    back = ops.unpack_gathers(f32_parts, dp, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(flat))


# ---------------------------------------------------------------------------
# eligibility predicates + kernel registry + capability tags
# ---------------------------------------------------------------------------
def test_wire_eligible():
    ok = dict(compress="int8", platform="cpu")
    assert ops.wire_eligible((N,), jnp.float32, **ok)
    assert ops.wire_eligible((8, WIRE_BLOCK), jnp.float32, **ok)
    assert not ops.wire_eligible((N,), jnp.float32, compress=None,
                                 platform="cpu")            # uncompressed
    assert not ops.wire_eligible((N - 1,), jnp.float32, **ok)  # % block
    assert not ops.wire_eligible((N,), jnp.bfloat16, **ok)     # payload dtype
    assert not ops.wire_eligible((N,), jnp.float32, compress="int8",
                                 platform="weird")
    # no size cap on any platform: the kernels tile rows
    big = (1 << 27,)
    assert ops.wire_eligible(big, jnp.float32, compress="int8", platform="cpu")
    assert ops.wire_eligible(big, jnp.float32, compress="int8",
                             platform="tpu")


def test_eligible_at_qwen2_zero1_dp4_sizes():
    """qwen2-0.5b's ZeRO-1 layout at dp=4 on TPU (494,032,768 parameters):
    the padded flat vector takes the fused pack, and one rank's ~123.5M
    element hop chunk takes the fused hops — the kernels tile rows, so
    no size falls back to lax."""
    from repro.optim.adamw import zero1_padded_size

    padded = zero1_padded_size(494_032_768, 4)
    assert padded % (4 * WIRE_BLOCK) == 0
    assert ops.pack_eligible(padded, 4, 1, platform="tpu")
    for compress in ("int8", "bf16"):
        assert ops.wire_eligible((padded // 4,), jnp.float32, compress,
                                 platform="tpu")
    # a segment of whole wire blocks or shorter than one block tiles; a
    # ragged segment longer than one block does not
    assert not ops.pack_eligible(4 * (WIRE_BLOCK + 8), 4, 1, platform="tpu")


def test_pack_eligible():
    assert ops.pack_eligible(64, 4, 2, platform="cpu")
    assert not ops.pack_eligible(63, 4, 2, platform="cpu")   # divisibility
    assert not ops.pack_eligible(64, 4, 2, platform="weird")
    assert not ops.pack_eligible(0, 4, 2, platform="cpu")


def test_registry_modes():
    from repro import kernels as reg

    assert reg.kernel_mode("ring_wire", "cpu") == "pallas"
    assert reg.kernel_mode("ring_wire", "weird") == "lax"
    assert reg.kernel_mode("no_such_kernel", "cpu") == "lax"
    mode, mod = reg.resolve("ring_wire", "cpu")
    assert mode == "pallas" and mod is ops
    mode, fn = reg.resolve("flash_attention", "cpu")
    assert mode == "pallas" and callable(fn)


def test_capabilities_wire_kernel_tag(mesh1):
    import repro.core as C

    caps = C.pax_init(mesh1, impl="ring-int8").capabilities()
    assert caps["reduce_scatter"]["wire_kernel"] == "pallas"
    assert caps["allgather"]["wire_kernel"] == "lax"  # nothing to dequantize
    plain = C.pax_init(mesh1, impl="ring").capabilities()
    assert plain["reduce_scatter"]["wire_kernel"] == "lax"
    # non-ring backends don't grow the tag at all
    paxi = C.pax_init(mesh1, impl="paxi").capabilities()
    assert "wire_kernel" not in paxi["reduce_scatter"]


# ---------------------------------------------------------------------------
# hlo_analysis: the traffic breakdown that proves the fusion claim
# ---------------------------------------------------------------------------
def test_wire_breakdown_fused_vs_lax():
    from repro.core.backends.ring import _quantize
    from repro.launch.hlo_analysis import wire_breakdown

    k1, k2 = jax.random.split(KEY)
    x, a = _vec(k1), _vec(k2)
    q_l, s_l = _quantize(x, "int8")
    q_f, s_f = ops.quant(x, "int8", interpret=True)

    lax_bd = wire_breakdown(lambda q, s, ad: ref.lax_hop_global(q, s, ad),
                            q_l, s_l, a)
    fus_bd = wire_breakdown(
        lambda q, s, ad: ops.hop_add_quant(q, s, ad, "int8", interpret=True),
        q_f, s_f, a)

    # the lax hop materializes dequantize + quantize intermediates
    assert lax_bd.bytes_by_class.get("dequantize", 0) > 0
    assert lax_bd.bytes_by_class.get("quantize", 0) > 0
    # the fused hop materializes NONE — only the kernel outputs
    assert fus_bd.bytes_by_class.get("quantize", 0) == 0
    assert fus_bd.bytes_by_class.get("dequantize", 0) == 0
    assert fus_bd.count_by_class.get("kernel", 0) == 1
    ratio = fus_bd.materialized_bytes / lax_bd.materialized_bytes
    assert ratio <= 0.5, f"fused/lax materialized bytes {ratio:.3f}"


def test_collective_stats_hbm_by_op():
    from repro.launch.hlo_analysis import collective_bytes

    hlo = """
  %p0 = f32[128]{0} parameter(0)
  %ar = f32[128]{0} all-reduce(f32[128]{0} %p0), replica_groups={}
  %ag = f32[256]{0} all-gather(f32[128]{0} %ar), dimensions={0}
"""
    stats = collective_bytes(hlo)
    assert stats.hbm_by_op["all-reduce"] == 2 * 128 * 4  # in + out
    assert stats.hbm_by_op["all-gather"] == (128 + 256) * 4
    assert stats.total_hbm_bytes == sum(stats.hbm_by_op.values())


# ---------------------------------------------------------------------------
# attention_impl routing through the registry
# ---------------------------------------------------------------------------
def test_attention_flash_matches_xla():
    import dataclasses

    from repro.configs.base import ModelConfig
    from repro.models.attention import attention, init_attention

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=128,
                      d_ff=256, vocab_size=64, num_heads=4, num_kv_heads=2,
                      param_dtype="float32", compute_dtype="float32",
                      attention_impl="flash")
    params = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128), jnp.float32)
    positions = jnp.arange(128)[None, :].repeat(2, axis=0)
    out_flash, _ = attention(params, x, cfg, positions=positions)
    cfg_xla = dataclasses.replace(cfg, attention_impl="xla")
    out_xla, _ = attention(params, x, cfg_xla, positions=positions)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla),
                               atol=3e-5, rtol=3e-5)
