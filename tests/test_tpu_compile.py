"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel for a described v5e chip (no chip
attached) and checks that the chip's compiler accepts it and that the
program holds the Mosaic kernel (``tpu_custom_call``).  Interpret mode
cannot show what this catches: a block view that does not tile, a kernel
that needs more VMEM than it may use, a program that does not fit.

Widths: qwen2-0.5b (494,032,768 parameters) trained ZeRO-1 at dp=4 — the
flat vector padded to whole wire blocks per rank, each ring hop carrying
one rank's chunk — and its attention at S=2048, 14 heads of 64.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

DP = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent cache
    # but not read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def padded():
    """qwen2-0.5b's ZeRO-1 flat length at dp=4."""
    import repro.configs as cfgs
    from repro.models import build_model
    from repro.optim.adamw import zero1_padded_size

    api = build_model(cfgs.get_config("qwen2-0.5b"))
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    return zero1_padded_size(n, DP)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I8, BF16 = jnp.float32, jnp.int8, jnp.bfloat16


@pytest.mark.parametrize("kernel", ["quant_i8", "hop_add_quant_i8",
                                    "hop_accum_i8", "hop_add_quant_bf16",
                                    "hop_accum_bf16"])
def test_ring_hop_kernel_compiles(one_chip, padded, kernel):
    from repro.kernels.ring_wire import ops

    c = padded // DP                   # one rank's chunk: ~123.5M elements
    compress = "int8" if kernel.endswith("i8") else "bf16"
    assert ops.wire_eligible((c,), F32, compress, platform="tpu")
    chunk, scales = (c,), (c // ops.WIRE_BLOCK, 1)
    q = functools.partial
    cases = {
        "quant_i8": (q(ops.quant, compress="int8", interpret=False),
                     [(chunk, F32)]),
        "hop_add_quant_i8": (
            lambda w, s, a: ops.hop_add_quant(w, s, a, "int8",
                                              interpret=False),
            [(chunk, I8), (scales, F32), (chunk, F32)]),
        "hop_accum_i8": (
            lambda w, s, a: ops.hop_accum(w, s, a, "int8", interpret=False),
            [(chunk, I8), (scales, F32), (chunk, F32)]),
        "hop_add_quant_bf16": (
            lambda w, a: ops.hop_add_quant(w, None, a, "bf16",
                                           interpret=False),
            [(chunk, BF16), (chunk, F32)]),
        "hop_accum_bf16": (
            lambda w, a: ops.hop_accum(w, None, a, "bf16", interpret=False),
            [(chunk, BF16), (chunk, F32)]),
    }
    fn, shapes = cases[kernel]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kernel", ["pack_parts", "pack_parts_ef",
                                    "unpack_gathers"])
def test_zero1_pack_kernel_compiles(one_chip, padded, kernel):
    from repro.kernels.ring_wire import ops

    assert ops.pack_eligible(padded, DP, 1, platform="tpu")
    flat = ((padded,), F32)
    cases = {
        "pack_parts": (
            lambda g: ops.pack_parts(g, DP, 1, F32, interpret=False), [flat]),
        "pack_parts_ef": (
            lambda g, e: ops.pack_parts_ef(g, e, DP, 1, interpret=False),
            [flat, flat]),
        "unpack_gathers": (
            lambda o: ops.unpack_gathers([o], DP, interpret=False), [flat]),
    }
    fn, shapes = cases[kernel]
    _compile(fn, one_chip, *shapes)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_mha

    S, H, HKV, D = 2048, 14, 2, 64      # qwen2-0.5b heads at a 2k context
    _compile(functools.partial(flash_mha, interpret=False), one_chip,
             ((1, S, H, D), BF16), ((1, S, HKV, D), BF16),
             ((1, S, HKV, D), BF16))
