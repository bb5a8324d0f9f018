"""Plain float32 reference of the dense decoder stack (Qwen2, ChatGLM3).

Straight ``jax.numpy`` at ``precision=highest``: RMSNorm, rotary on the
first ``rope_fraction`` of each head (interleaved pairs), grouped-query
causal attention, SwiGLU MLP, final norm and head.  No cache, no batching
of requests, no kernels; it imports nothing of the program and takes its
weights from ``bench.weights`` one layer at a time, so a 14-layer
ChatGLM3 stage needs one layer's float32 weights on the device at once.

Attention runs in blocks of query rows, so an 8k sequence never holds its
whole score matrix.

``matmul`` selects the precision: :func:`mm_f32` for the reference,
:func:`mm_fp8` for the control (every matrix product's operands rounded
to float8 e4m3 with a per-tensor scale, the precision one step below the
configuration's bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W

#: query rows per attention block
Q_BLOCK = 1024

_HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def mm_f32(a, b, spec):
    return jnp.einsum(spec, a, b, precision=_HI)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm_fp8(a, b, spec):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=_HI)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta, fraction):
    """x: (S, H, D); rotary on the first ``fraction`` of D, pairs (0,1),
    (2,3), ...; the rest passes through."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)
    ang = positions[:, None].astype(jnp.float32) * inv        # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(*x.shape[:-1], rot), x[..., rot:]], -1)


def layer(w, x, dims: W.Dims, mm):
    """One decoder layer over one sequence ``x`` (S, d)."""
    S = x.shape[0]
    hd, H, K = dims.head_dim, dims.heads, dims.kv_heads
    pos = jnp.arange(S)
    h = rmsnorm(x, w["ln1"], dims.norm_eps)
    q, k, v = (mm(h, w["wq"], "sd,de->se"), mm(h, w["wk"], "sd,de->se"),
               mm(h, w["wv"], "sd,de->se"))
    if dims.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(S, H, hd), pos, dims.rope_theta, dims.rope_fraction)
    k = rope(k.reshape(S, K, hd), pos, dims.rope_theta, dims.rope_fraction)
    v = v.reshape(S, K, hd)
    q = q.reshape(S, K, H // K, hd)
    outs = []
    for b0 in range(0, S, Q_BLOCK):
        b1 = min(S, b0 + Q_BLOCK)
        s = mm(q[b0:b1], k[:b1], "qkgd,tkd->kgqt") / np.sqrt(hd)
        mask = jnp.arange(b0, b1)[:, None] >= jnp.arange(b1)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(mm(p, v[:b1], "kgqt,tkd->qkgd"))
    a = jnp.concatenate(outs, 0).reshape(S, H * hd)
    x = x + mm(a, w["wo"], "se,ed->sd")
    h = rmsnorm(x, w["ln2"], dims.norm_eps)
    f = jax.nn.silu(mm(h, w["wg"], "sd,df->sf")) * mm(h, w["wi"], "sd,df->sf")
    return x + mm(f, w["wd"], "sf,fd->sd")


_weights = jax.jit(W.layer_weights, static_argnames=("dims",))
_embed_weights = jax.jit(W.embed_weights, static_argnames=("dims",))
_layer = jax.jit(layer, static_argnames=("dims", "mm"))


@functools.partial(jax.jit, static_argnames=("dims", "mm"))
def _logits(e, h, dims, mm):
    h = rmsnorm(h, e["final_norm"], dims.norm_eps)
    head = e["tok"].T if dims.tied else e["unembed"]
    return mm(h, head, "sd,dv->sv")


def logits_at(seed: int, dims: W.Dims, seqs: list[np.ndarray],
              rows: list[np.ndarray], *, pad_to: int, rows_pad: int,
              matmul: str = "f32") -> list[np.ndarray]:
    """Float32 logits of ``seqs[i]`` at positions ``rows[i]``.

    Every sequence is padded to ``pad_to`` tokens and every row list to
    ``rows_pad`` rows, so a cell compiles one shape of each program
    (causal attention keeps the pad out of the real rows).  Sequences run
    layer by layer, with that layer's weights made from the seed and freed
    before the next.  Returns one ``(len(rows[i]), vocab)`` array each.
    """
    mm = MATMULS[matmul]
    k_embed, k_layers = W.keys(seed)
    with jax.default_matmul_precision("highest"):
        e = _embed_weights(k_embed, dims)
        xs = []
        for s in seqs:
            t = np.zeros((pad_to,), np.int32)
            t[:len(s)] = s
            xs.append(e["tok"][jnp.asarray(t)])
        for i in range(dims.layers):
            w = _weights(jax.random.fold_in(k_layers, i), dims)
            xs = [_layer(w, x, dims, mm) for x in xs]
            del w
        out = []
        for x, r in zip(xs, rows):
            idx = np.zeros((rows_pad,), np.int32)
            idx[:len(r)] = r
            out.append(np.asarray(_logits(e, x[jnp.asarray(idx)], dims, mm))
                       [:len(r)])
        return out
