"""Plain float32 reference of the first training steps of a dense decoder.

Per sequence: the forward of ``reference/dense.py`` (each layer
recomputed in the backward pass so a 2k sequence fits), the head, token-
mean cross entropy plus the z-loss term ``1e-4 * lse**2`` of the training
recipe, and its gradient.  The step's gradient is the mean over the global
batch.  AdamW as configured (bias-corrected moments, decoupled weight
decay, global-norm clipping), all in float32; the parameters are stored in
the type the configuration states (bfloat16), so each update is rounded
to it.  Sequences spread over the devices given; the optimizer runs on the
first.  Imports nothing of the program; weights come from
``bench.weights``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from . import dense

Z_LOSS = 1e-4


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def init_params(seed: int, dims: W.Dims) -> dict:
    """Float32 parameters: ``{"embed": {...}, "layers": {name: (L, ...)}}``."""
    k_embed, k_layers = W.keys(seed)
    e = jax.jit(W.embed_weights, static_argnames=("dims",))(k_embed, dims)
    layers = jax.jit(lambda k: jax.lax.map(
        lambda i: W.layer_weights(jax.random.fold_in(k, i), dims),
        jnp.arange(dims.layers)))(k_layers)
    return {"embed": e, "layers": layers}


def seq_loss(p, tokens, targets, dims, mm):
    x = p["embed"]["tok"][tokens]
    layer = jax.checkpoint(lambda x, w: dense.layer(w, x, dims, mm))
    x, _ = jax.lax.scan(lambda x, w: (layer(x, w), None), x, p["layers"])
    h = dense.rmsnorm(x, p["embed"]["final_norm"], dims.norm_eps)
    head = p["embed"]["tok"].T if dims.tied else p["embed"]["unembed"]
    logits = mm(h, head, "sd,dv->sv")
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.mean(lse - ll) + Z_LOSS * jnp.mean(lse * lse)


_grad = jax.jit(jax.value_and_grad(seq_loss), static_argnames=("dims", "mm"))
_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))


@functools.partial(jax.jit, static_argnames=("store",))
def _adam(p, m, v, g, t, lr, b1, b2, eps, wd, clip, store):
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)

    def upd(p, m, v):
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        new = p - lr * (step + wd * p)
        return new.astype(store).astype(jnp.float32)
    return jax.tree.map(upd, p, m, v), m, v, g, gnorm


def leaf_norms(tree) -> dict:
    """``{"embed/tok": norm, "layers/wq": norm over all layers, ...}``."""
    out = {}
    for group, leaves in tree.items():
        for name, x in leaves.items():
            out[f"{group}/{name}"] = float(jnp.sqrt(jnp.sum(
                jnp.square(x.astype(jnp.float32)))))
    return out


def train(seed: int, dims: W.Dims, batches: list[dict], adam: Adam,
          devices, *, matmul: str = "f32") -> dict:
    """Run ``len(batches)`` steps; return the losses, the first step's
    pre-clip gradient norm, the clipped first gradient's leaf norms, and
    the leaf norms of the parameters' change over all the steps."""
    mm = dense.MATMULS[matmul]
    opt_dev, workers = devices[0], (devices[1:] or devices[:1])
    with jax.default_matmul_precision("highest"):
        p = jax.device_put(init_params(seed, dims), opt_dev)
        p0 = p
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        out = {"losses": []}
        for t, b in enumerate(batches, start=1):
            rows = b["tokens"].shape[0]
            copies = {d: jax.device_put(p, d) for d in workers}
            acc, losses = {}, []
            for i in range(rows):
                d = workers[i % len(workers)]
                loss, g = _grad(copies[d], jax.device_put(b["tokens"][i], d),
                                jax.device_put(b["targets"][i], d), dims, mm)
                acc[d] = g if d not in acc else _add(acc[d], g)
                losses.append(loss)
            del copies
            total = None
            for d, g in acc.items():
                g = jax.device_put(g, opt_dev)
                total = g if total is None else _add(total, g)
            del acc
            total = jax.tree.map(lambda x: x / rows, total)
            p, m, v, g, gnorm = _adam(p, m, v, total, float(t), adam.lr,
                                      adam.b1, adam.b2, adam.eps,
                                      adam.weight_decay, adam.grad_clip,
                                      jnp.dtype(dims.param_dtype))
            out["losses"].append(float(np.mean([float(x) for x in losses])))
            if t == 1:
                out["grad_norm"] = float(gnorm)
                out["grad_leaves"] = leaf_norms(g)
            del total, g
        out["change_leaves"] = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
        return out
