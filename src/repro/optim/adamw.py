"""AdamW, pure JAX, in two layouts:

* **tree**: classic per-leaf moments (used in "gspmd" mode, where XLA shards
  optimizer state like the params via in_shardings);
* **flat/ZeRO-1**: moments live only for this data-parallel rank's shard of
  the flattened gradient vector (used in "abi" mode: the gradient is
  reduce-scattered through the ABI, the update is computed on the shard,
  and the update vector is all-gathered back — DeepSpeed-style ZeRO-1).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels import WIRE_BLOCK


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


class AdamState(NamedTuple):
    step: jax.Array
    m: jax.typing.ArrayLike
    v: jax.typing.ArrayLike


def init_tree(params) -> AdamState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(jnp.zeros((), jnp.int32), zeros,
                     jax.tree.map(jnp.copy, zeros))


def update_tree(cfg: AdamWConfig, grads, state: AdamState, params, lr_scale=1.0):
    step = state.step + 1
    t = step.astype(jnp.float32)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m2 = cfg.b1 * m + (1 - cfg.b1) * g
        v2 = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m2 / (1 - cfg.b1 ** t)
        vhat = v2 / (1 - cfg.b2 ** t)
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - cfg.lr * lr_scale * delta).astype(p.dtype), m2, v2

    out = jax.tree.map(upd, grads, state.m, state.v, params)
    new_params = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_params, AdamState(step, new_m, new_v), gnorm


# ---------------------------------------------------------------------------
# flat / ZeRO-1
# ---------------------------------------------------------------------------
class FlatAdamState(NamedTuple):
    step: jax.Array
    m: jax.Array   # (shard,) f32 — only this dp-rank's shard
    v: jax.Array
    #: error-feedback buffer.  Per-rank state (each rank's own quantization
    #: residual over the FULL flat vector), so the global-view layout is
    #: (dp * padded,) sharded over the dp axes — every rank sees its
    #: (padded,) residual inside the train step's shard_map region.  A
    #: (dp,)-shaped dummy (one element per rank) when compression is off.
    ef: jax.Array


def flat_size(params) -> int:
    return sum(int(jnp.size(jax.eval_shape(lambda: p) if callable(p) else p))
               for p in jax.tree.leaves(params))


def flatten(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])


def unflatten_like(vec, tree):
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for l in leaves:
        n = int(jnp.size(l)) if not hasattr(l, "size") else int(l.size)
        out.append(vec[off:off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def zero1_pad_multiple(dp_size: int, buckets: int = 1) -> int:
    """The ZeRO-1 flat vector is padded to a multiple of this: every rank's
    bucket segment then holds whole ring-wire blocks, which the fused
    pack/unpack and hop kernels tile (``kernels.WIRE_BLOCK``)."""
    return dp_size * max(buckets, 1) * WIRE_BLOCK


def zero1_padded_size(n: int, dp_size: int, buckets: int = 1) -> int:
    """Flat-vector length padded to :func:`zero1_pad_multiple` — the shared
    contract between ``init_flat_global``, ``grad_sync.zero1_step``
    bucketing and the train-loop wiring."""
    m = zero1_pad_multiple(dp_size, buckets)
    return -(-n // m) * m


def init_flat_global(params, dp_size: int, *, buckets: int = 1,
                     with_ef: bool = False) -> FlatAdamState:
    """Global-view flat optimizer state: (padded,) moment vectors meant to be
    sharded over the dp axes (each rank sees its (padded/dp,) shard inside
    the train step's shard_map region).  With ``with_ef`` the error-feedback
    buffer is (dp * padded,) — per-rank full-length residuals, sharded the
    same way (see :class:`FlatAdamState`)."""
    n = sum(int(p.size) for p in jax.tree.leaves(params))
    padded = zero1_padded_size(n, dp_size, buckets)
    return FlatAdamState(
        jnp.zeros((), jnp.int32),
        jnp.zeros((padded,), jnp.float32),
        jnp.zeros((padded,), jnp.float32),
        jnp.zeros((dp_size * padded if with_ef else dp_size,), jnp.float32),
    )


def init_flat(params, dp_size: int, with_ef: bool) -> FlatAdamState:
    n = sum(int(p.size) for p in jax.tree.leaves(params))
    padded = zero1_padded_size(n, dp_size)
    shard = padded // dp_size
    return FlatAdamState(
        jnp.zeros((), jnp.int32),
        jnp.zeros((shard,), jnp.float32),
        jnp.zeros((shard,), jnp.float32),
        jnp.zeros((padded if with_ef else 1,), jnp.float32),
    )


def update_flat_shard(cfg: AdamWConfig, g_shard, state: FlatAdamState,
                      p_shard, gnorm, lr_scale=1.0):
    """AdamW on this rank's flat shard. g_shard/p_shard: (shard,) f32."""
    step = state.step + 1
    t = step.astype(jnp.float32)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    g = g_shard * scale
    m2 = cfg.b1 * state.m + (1 - cfg.b1) * g
    v2 = cfg.b2 * state.v + (1 - cfg.b2) * jnp.square(g)
    mhat = m2 / (1 - cfg.b1 ** t)
    vhat = v2 / (1 - cfg.b2 ** t)
    delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p_shard
    new_p_shard = p_shard - cfg.lr * lr_scale * delta
    return new_p_shard, FlatAdamState(step, m2, v2, state.ef)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------
def warmup_cosine(step, *, warmup: int, total: int, floor: float = 0.1):
    t = step.astype(jnp.float32)
    wu = jnp.minimum(t / jnp.maximum(warmup, 1), 1.0)
    prog = jnp.clip((t - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
    cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return wu * cos
