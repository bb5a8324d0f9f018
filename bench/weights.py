"""Seeded weights for the dense decoder family, made by the benchmark.

One function, :func:`layer_weights`, defines every layer's values from the
seed and the layer index; :func:`embed_weights` does the same for the
embedding, the head and the final norm.  Values are drawn in float32 and
rounded to bfloat16, the type they are served in, so the program and the
float32 reference see the same numbers.

:func:`program_params` lays them out as the program's parameter tree (one
jitted call on the device, every leaf in the dtype the program's own
``init`` gives it).  The reference (``bench/reference``) calls the same
generators one layer at a time and never sees the program's arrays.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Dims:
    """The widths one dense layer stack needs."""
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    rope_fraction: float
    norm_eps: float
    param_dtype: str = "bfloat16"

    @classmethod
    def from_file(cls, c: dict) -> "Dims":
        """From a configuration file of ``bench/configs`` (published key
        names of either Hugging Face layout; ``reference`` holds the sizes
        the file assumes)."""
        def get(*names, default=None):
            for n in names:
                if n in c:
                    return c[n]
            if default is None:
                raise KeyError(f"{c.get('arch')}: none of {names}")
            return default
        ref = c.get("reference", {})
        d, h = get("hidden_size"), get("num_attention_heads")
        return cls(get("num_hidden_layers", "num_layers"), d,
                   get("intermediate_size", "ffn_hidden_size"), h,
                   get("num_key_value_heads", "multi_query_group_num"),
                   get("head_dim", "kv_channels", default=d // h),
                   get("vocab_size", "padded_vocab_size"),
                   bool(get("tie_word_embeddings", default=False)),
                   bool(get("add_qkv_bias", default=ref.get("qkv_bias", False))),
                   float(get("rope_theta", default=ref.get("rope_theta", 1e4))),
                   float(ref.get("rope_fraction", 1.0)),
                   float(get("rms_norm_eps", "layernorm_epsilon")),
                   str(get("torch_dtype")))

    @classmethod
    def of(cls, cfg, norm_eps: float) -> "Dims":
        """From a ``repro.configs`` ModelConfig (dense family only): what
        the program runs, checked against :meth:`from_file`."""
        if cfg.family != "dense" or cfg.activation != "swiglu" \
                or cfg.norm != "rmsnorm":
            raise ValueError(f"{cfg.name}: only dense SwiGLU/RMSNorm stacks "
                             "have seeded weights and a reference here")
        return cls(cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.num_heads,
                   cfg.num_kv_heads, cfg.resolved_head_dim, cfg.vocab_size,
                   cfg.tie_embeddings, cfg.qkv_bias, cfg.rope_theta,
                   cfg.rope_fraction, norm_eps, cfg.param_dtype)


#: standard deviation of a logit at the head: large enough that the top
#: logits are spread out as in a trained model's confident positions
LOGIT_STD = 2.5


def base_key(seed: int):
    """A PRNG key from any non-negative seed (beyond 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              (seed // 2**31) % 2**32)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mat(key, n_in, n_out, scale=1.0):
    return _bf16(jax.random.normal(key, (n_in, n_out)) * (scale / n_in ** 0.5))


def _vec(key, n, mean, std):
    return _bf16(mean + std * jax.random.normal(key, (n,)))


def layer_weights(key, dims: Dims) -> dict:
    """Layer weights from its own key (``fold_in(layers_key, index)``)."""
    d, hd = dims.d_model, dims.head_dim
    q, kv = dims.heads * hd, dims.kv_heads * hd
    ks = jax.random.split(key, 12)
    w = {
        "ln1": _vec(ks[0], d, 1.0, 0.1),
        "wq": _mat(ks[1], d, q),
        "wk": _mat(ks[2], d, kv),
        "wv": _mat(ks[3], d, kv),
        "wo": _mat(ks[4], q, d),
        "ln2": _vec(ks[5], d, 1.0, 0.1),
        "wg": _mat(ks[6], d, dims.d_ff),
        "wi": _mat(ks[7], d, dims.d_ff),
        "wd": _mat(ks[8], dims.d_ff, d),
    }
    if dims.qkv_bias:
        w["bq"] = _vec(ks[9], q, 0.0, 0.1)
        w["bk"] = _vec(ks[10], kv, 0.0, 0.1)
        w["bv"] = _vec(ks[11], kv, 0.0, 0.1)
    return w


def embed_weights(key, dims: Dims) -> dict:
    ks = jax.random.split(key, 3)
    d = dims.d_model
    w = {"final_norm": _vec(ks[0], d, 1.0, 0.1)}
    if dims.tied:
        # the head is the embedding's transpose: scale it for the logits
        w["tok"] = _bf16(jax.random.normal(ks[1], (dims.vocab, d))
                         * (LOGIT_STD / d ** 0.5))
    else:
        w["tok"] = _bf16(jax.random.normal(ks[1], (dims.vocab, d)))
        w["unembed"] = _mat(ks[2], d, dims.vocab, LOGIT_STD)
    return w


def keys(seed: int):
    """``(embed_key, layers_key)``; layer ``l`` uses ``fold_in(layers_key, l)``."""
    k = base_key(seed)
    return jax.random.fold_in(k, 1), jax.random.fold_in(k, 2)


def program_params(api, dims: Dims, seed: int):
    """The program's parameter tree for ``api`` (``repro.models.build_model``),
    filled from the seed on the device in one jitted call."""
    like = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    k_embed, k_layers = keys(seed)

    def build(k_embed, k_layers):
        e = embed_weights(k_embed, dims)
        lw = jax.lax.map(
            lambda i: layer_weights(jax.random.fold_in(k_layers, i), dims),
            jnp.arange(dims.layers))
        attn = {n: lw[n] for n in ("wq", "wk", "wv", "wo")}
        if dims.qkv_bias:
            attn.update({n: lw[n] for n in ("bq", "bk", "bv")})
        embed = {"tok": e["tok"]}
        if not dims.tied:
            embed["unembed"] = e["unembed"]
        tree = {
            "embed": embed,
            "layers": {"ln1": {"scale": lw["ln1"]}, "attn": attn,
                       "ln2": {"scale": lw["ln2"]},
                       "mlp": {"wg": lw["wg"], "wi": lw["wi"], "wo": lw["wd"]}},
            "final_norm": {"scale": e["final_norm"]},
        }
        if jax.tree.structure(tree) != jax.tree.structure(like):
            raise ValueError("the program's parameter tree changed: "
                             f"{jax.tree.structure(like)}")
        return jax.tree.map(lambda v, s: v.astype(s.dtype).reshape(s.shape),
                            tree, like)

    return jax.jit(build)(k_embed, k_layers)
