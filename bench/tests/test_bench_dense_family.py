"""The dense family module, found by the harness for a configuration that
names none, gives bit for bit what the benchmark made before there was a
family module: at the chat cell's rehearsal widths on the CPU, the
seeded parameter tree and the float32 reference's logits (and its float8
control's) are pinned."""
import hashlib

import jax
import numpy as np
import pytest

from bench import harness as H
from bench.drivers._model import dims_for, model_config

CELL = "qwen2-0.5b.serve.chat"
SEED = 2**31 + 11

#: sha256 of the tree (each leaf's path, dtype, shape and bytes, in order)
PARAMS = "e4a1e5176228ef45a0140ca81379719c83d1bc18789ce94d87f9ace8ef39fc79"
#: sha256 of the float32 logits of SEQS at ROWS
LOGITS = {
    "f32": "50c36f34f03e0e9964e7f78d50c5f15c35a47c178efe1e07928778e2968282a5",
    "fp8": "3ca1362c3e8e66abe54e088c380aa647a296f40051f1520e58a7a52d88069247",
}


@pytest.fixture(scope="module")
def chat():
    cell = H.load_cell(CELL, H.load_bench())
    cfg = model_config(cell, rehearse=True)
    return H.family(cell), cfg, dims_for(cell, cfg, rehearse=True)


def test_a_configuration_that_names_no_module_is_dense(chat):
    family, _, _ = chat
    assert family.__name__ == "bench.reference.dense"


def test_seeded_parameters_are_pinned(chat):
    from repro.models import build_model
    family, cfg, dims = chat
    params = family.program_params(build_model(cfg), dims, SEED)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARAMS


@pytest.mark.parametrize("matmul", sorted(LOGITS))
def test_reference_logits_are_pinned(chat, matmul):
    family, _, dims = chat
    rng = np.random.default_rng(5)
    seqs = [rng.integers(1, dims.vocab, 40).astype(np.int32),
            rng.integers(1, dims.vocab, 17).astype(np.int32)]
    rows = [np.arange(30, 40), np.arange(0, 17, 3)]
    out = family.logits_at(SEED, dims, seqs, rows, pad_to=64, rows_pad=12,
                           matmul=matmul)
    assert [o.shape for o in out] == [(10, dims.vocab), (6, dims.vocab)]
    h = hashlib.sha256()
    for o in out:
        h.update(np.ascontiguousarray(o, np.float32).tobytes())
    assert h.hexdigest() == LOGITS[matmul]
