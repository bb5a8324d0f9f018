"""Operations per token from a configuration's shapes, for utilization.

Counts the matrix products a dense decoder needs, two operations per
multiply-add: the projections and MLP of every layer, causal attention's
two products over the context, and the head (the embedding lookup costs
none; a tied head is counted once, as a product).  Training is forward
plus backward, three times the forward; recomputation (remat) is left
out, as model FLOP utilization requires.
"""
from __future__ import annotations

from .weights import Dims


def matmul_params(d: Dims) -> int:
    """Weights that take part in a matrix product per token."""
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_layer = d.d_model * (q + 2 * kv) + q * d.d_model + 3 * d.d_model * d.d_ff
    return d.layers * per_layer + d.d_model * d.vocab


def forward_flops_per_token(d: Dims, context: int) -> float:
    """One token's forward pass at mean causal context ``context`` (for a
    whole sequence of length S, ``(S + 1) / 2``)."""
    attn = 2 * 2 * d.layers * d.heads * d.head_dim * context
    return 2.0 * matmul_params(d) + attn


def train_flops_per_token(d: Dims, seq_len: int) -> float:
    """Forward and backward per trained token of ``seq_len``-long rows."""
    return 3.0 * forward_flops_per_token(d, (seq_len + 1) / 2)
