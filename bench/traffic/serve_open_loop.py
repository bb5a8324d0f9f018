"""Open-loop request generator for serving cells.

Reads a traffic file's parameters and returns the requests due from
``-lead_in_s`` to ``seconds`` at ``rate_per_s`` (the window opens at 0;
the lead-in brings the engine to its steady state first).  Arrivals are a
Poisson process conditioned on its count: ``n = round(rate * span)``
requests, with the gaps of ``n + 1`` exponential draws scaled to fill the
span.  The gaps and
the (prompt length, output length) pairs, in order, come from the
traffic file's fixed ``shape_seed``, so every run seed offers the same
work on the same schedule; the run seed draws the prompt tokens (and the
driver the weights).  With a few dozen requests in a window the 95th
percentile of time to first token rests on which long prompts arrive
together: permuting the schedule per seed spread it by 31-41 % over six
seeds on a TPU v5e, against at most 11 % between two runs of one seed.
Every request samples as the file says (``temperature``, ``top_k``;
temperature 0 is greedy).  Requests are due whether or not earlier ones
have finished.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Offered:
    """One request as offered: due ``due_s`` seconds after the window opens."""
    idx: int
    due_s: float
    prompt: np.ndarray          # (prompt_len,) int32 token ids
    max_new_tokens: int
    temperature: float
    top_k: int


def _lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def generate(params: dict, *, seed: int, rate_per_s: float, seconds: float,
             vocab_size: int, lead_in_s: float = 0.0) -> list[Offered]:
    if params.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {params.get('arrivals')!r}")
    span = seconds + lead_in_s
    n = max(1, int(round(rate_per_s * span)))
    fixed = np.random.default_rng(params["shape_seed"])
    gaps = fixed.exponential(1.0, n + 1)
    prompt_lens = _lengths(params["prompt_len"], fixed, n)
    out_lens = _lengths(params["output_len"], fixed, n)

    run = np.random.default_rng(seed)
    due = np.cumsum(gaps)[:n] / gaps.sum() * span - lead_in_s
    out = []
    for i in range(n):
        prompt = run.integers(1, vocab_size, int(prompt_lens[i])).astype(np.int32)
        out.append(Offered(i, float(due[i]), prompt, int(out_lens[i]),
                           float(params["temperature"]), int(params["top_k"])))
    return out
