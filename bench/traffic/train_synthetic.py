"""Packed synthetic documents for training cells.

Each step's rows are documents packed back to back, separated by token 0,
and cut into ``seq_len + 1`` tokens; the model sees the first ``seq_len``
and is trained on the next token at each position.  Document lengths come
from the traffic file's fixed ``shape_seed`` (every run seed packs the same
lengths), token ids from a Zipf-like unigram drawn from the run seed and
the step index, so every step's rows differ.
"""
from __future__ import annotations

import numpy as np


def _doc_lengths(spec: dict, rng: np.random.Generator, total: int) -> list[int]:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out, n = [], 0
    while n < total:
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"])
        k = int(np.clip(round(x), spec["min"], spec["max"]))
        out.append(k)
        n += k + 1
    return out


def batch(params: dict, *, seed: int, step: int, global_batch: int,
          seq_len: int, vocab_size: int) -> dict:
    """``{"tokens", "targets"}``, each ``(global_batch, seq_len)`` int32."""
    width = seq_len + 1
    lengths = _doc_lengths(params["doc_len"],
                           np.random.default_rng([params["shape_seed"], step]),
                           global_batch * width)
    rng = np.random.default_rng([seed % 2**63, step])
    parts = []
    for k in lengths:
        ranks = rng.zipf(params["zipf_a"], size=k)
        parts.append((ranks % (vocab_size - 1) + 1).astype(np.int32))
        parts.append(np.zeros((1,), np.int32))            # separator
    flat = np.concatenate(parts)[:global_batch * width]
    rows = flat.reshape(global_batch, width)
    return {"tokens": rows[:, :-1].copy(), "targets": rows[:, 1:].copy()}
