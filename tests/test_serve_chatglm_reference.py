"""The serving engine against the float32 reference on a small
ChatGLM3-shaped model.

Seeded weights (``bench/weights.py``) go into a dense stack shaped as
ChatGLM3 is: rotary on half of each head (``rope_fraction`` 0.5), 16 query
heads over 1 or 2 KV heads, QKV bias, an untied head.  Two greedy
requests go through ``ServeEngine.submit``/``step`` together: prompts of
three and four chunks, each with a ragged last chunk, then decoding
through the paged cache.  The logits of every real prefill position and
of every decoding row are caught as the compiled programs return them,
and compared with ``bench/reference/dense.py``'s full forward pass over
the prompt and the served tokens.  A planted fault, rotary over the whole
head in the program only, has to fail the comparison.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.configs as cfgs
from repro.core.compat import make_mesh
from repro.models import build_model
from repro.runtime.dist import make_dist
from repro.serve.engine import Request, ServeEngine

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import weights as W  # noqa: E402
from bench.reference import dense as R  # noqa: E402

SEED = 2**31 + 17
CHUNK = 8
MAX_SEQ = 48
#: 29 positions: four chunks, the last holding 5; 19: three, the last 3
PROMPTS = (29, 19)
NEW = 6

#: largest |program - reference| a logit may show, by the program's dtype.
#: float32: the paged cache holds K and V in bfloat16 whatever the compute
#: dtype (``init_paged_cache``), so each K and V is rounded to 8 bits of
#: mantissa; that moves logits of spread 2.5 by up to 0.019 here (the
#: plain forward, with no cache, agrees to 5e-6), and 0.05 leaves 2.5
#: times the room.
#: bfloat16: every activation is rounded as well (a relative 2**-9 a step,
#: through two layers and the head); the gap reaches 0.096 here, and 0.25
#: leaves 2.6 times the room.  Rotary over the whole head reads 2.4.
TOL = {"float32": 0.05, "bfloat16": 0.25}


def _config(kv_heads: int, dtype: str):
    return dataclasses.replace(
        cfgs.smoke_config("chatglm3-6b"), num_layers=2, d_model=256, d_ff=512,
        num_heads=16, num_kv_heads=kv_heads, head_dim=16, vocab_size=512,
        param_dtype=dtype, compute_dtype=dtype)


def _served(cfg, program_cfg=None):
    """Serve two greedy requests on ``program_cfg``'s program (``cfg``'s
    by default) with ``cfg``'s seeded weights; returns ``(requests,
    caught)``, ``caught`` as ``(rid, position, logits row)``."""
    dims = W.Dims.of(cfg, 1e-6)
    api = build_model(program_cfg or cfg)
    params = W.program_params(api, dims, SEED)
    dist = make_dist(make_mesh((1, 1), ("data", "model")), impl="paxi")
    eng = ServeEngine(api, params, max_batch=2, max_seq=MAX_SEQ, block_size=4,
                      prefill_chunk=CHUNK, dist=dist)
    sched, caught = eng.scheduler, []
    prefill_fn, decode_fn = eng._prefill_chunk_fn, eng._decode_paged

    def prefill(p, toks, pages, table, start):
        logits, pages = prefill_fn(p, toks, pages, table, start)
        seq = next(s for s in sched.slots if s is not None
                   and np.array_equal(s.table, np.asarray(table)[0]))
        start = int(start)
        for r in range(min(CHUNK, seq.prompt_len - start)):
            caught.append((seq.req.rid, start + r, np.asarray(logits[0, r])))
        return logits, pages

    def decode(p, tok, pages, tables, lengths):
        logits, pages = decode_fn(p, tok, pages, tables, lengths)
        for i in sched.decode_slots():
            caught.append((sched.slots[i].req.rid, int(lengths[i]),
                           np.asarray(logits[i])))
        return logits, pages

    eng._prefill_chunk_fn, eng._decode_paged = prefill, decode
    rng = np.random.default_rng(4)
    reqs = [Request(i, rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=NEW) for i, n in enumerate(PROMPTS)]
    eng.run(reqs)
    return reqs, caught


def _widest_gap(cfg, reqs, caught) -> float:
    dims = W.Dims.of(cfg, 1e-6)
    seqs = [np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
            for r in reqs]
    ref = R.logits_at(SEED, dims, seqs, [np.arange(len(s)) for s in seqs],
                      pad_to=MAX_SEQ, rows_pad=MAX_SEQ)
    return max(float(np.max(np.abs(row - ref[rid][pos])))
               for rid, pos, row in caught)


@pytest.mark.parametrize("dtype", sorted(TOL))
@pytest.mark.parametrize("kv_heads", [1, 2])
def test_prefill_and_decode_logits_match_the_reference(kv_heads, dtype):
    cfg = _config(kv_heads, dtype)
    assert cfg.rope_fraction == 0.5 and cfg.qkv_bias and not cfg.tie_embeddings
    reqs, caught = _served(cfg)
    for r in reqs:
        assert r.done and len(r.out_tokens) == NEW
        positions = sorted(pos for rid, pos, _ in caught if rid == r.rid)
        # every prompt position once, then one row a decode step
        assert positions == list(range(len(r.prompt) + NEW - 1))
    assert _widest_gap(cfg, reqs, caught) <= TOL[dtype]


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_full_rotary_in_the_program_fails_the_comparison(dtype):
    cfg = _config(2, dtype)
    reqs, caught = _served(cfg, dataclasses.replace(cfg, rope_fraction=1.0))
    assert _widest_gap(cfg, reqs, caught) > 5 * TOL[dtype]
