"""The traffic generators repeat exactly for a seed and differ across
seeds, while every seed offers the same sizes on the same schedule."""
import numpy as np
import pytest

from bench import harness

GEN = harness.load_module(harness.BENCH / "traffic" / "serve_open_loop.py", "traffic")
MIXES = ["chat", "docqa"]


def offer(mix, seed, rate=1.5, seconds=40.0):
    params = harness.load_json(harness.BENCH / "traffic" / f"{mix}.json")
    return GEN.generate(params, seed=seed, rate_per_s=rate, seconds=seconds,
                        vocab_size=65024)


def key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens, r.temperature,
             r.top_k) for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    assert key(offer(mix, 2**31 + 17)) == key(offer(mix, 2**31 + 17))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_but_offer_the_same_work(mix):
    a, b = offer(mix, 11), offer(mix, 12)
    assert key(a) != key(b)
    work = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens, r.temperature, r.top_k) for r in rs]
    assert work(a) == work(b)


@pytest.mark.parametrize("mix", MIXES)
def test_arrivals_fill_the_window_within_the_clips(mix):
    params = harness.load_json(harness.BENCH / "traffic" / f"{mix}.json")
    reqs = offer(mix, 5, rate=2.0, seconds=30.0)
    assert len(reqs) == 60
    due = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(due) >= 0) and 0 < due[0] and due[-1] < 30.0
    lo, hi = params["prompt_len"]["min"], params["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in reqs)
    assert all(1 <= t < 65024 for r in reqs for t in r.prompt[:4])
