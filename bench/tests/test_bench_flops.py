"""bench/flops.py against a count by hand for qwen2-0.5b."""

from bench import flops, harness
from bench.weights import Dims


def qwen():
    return Dims.from_file(harness.load_json(harness.BENCH / "configs" / "qwen2-0.5b.json"))


def test_matmul_params_by_hand():
    # per layer: q 896x896, k and v 896x128, o 896x896, MLP 3 x 896x4864
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert layer == 14_909_440
    head = 896 * 151_936
    assert flops.matmul_params(qwen()) == 24 * layer + head == 493_961_216


def test_train_flops_per_token_by_hand():
    # 6 x matmul params, plus causal attention: forward 2 products x 2 ops
    # x 24 layers x 14 heads x 64 dims x mean context (2048 + 1) / 2
    attn_fwd = 2 * 2 * 24 * 14 * 64 * (2049 / 2)
    want = 3 * (2 * 493_961_216 + attn_fwd)
    assert flops.train_flops_per_token(qwen(), 2048) == want


def test_params_match_the_published_count():
    # 494,032,768 published parameters = matmul weights + norms + biases
    d = qwen()
    norms = d.layers * 2 * d.d_model + d.d_model
    biases = d.layers * (d.heads + 2 * d.kv_heads) * d.head_dim
    assert flops.matmul_params(d) + norms + biases == 494_032_768
