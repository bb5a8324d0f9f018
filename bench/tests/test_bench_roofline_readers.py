"""The roofline readers of the model programs (``prefill_mfu.serve``,
``decode_hbm_share.serve``) on synthetic profiles of the chatglm3-6b
stage, worked by hand; and the docqa-8k cell against the file contract,
rehearsed on the CPU."""
import time
import types

import pytest

from bench import harness as H
from bench.metrics._trace import WINDOW, Event, Trace

CELL = "chatglm3-6b.serve.docqa-8k"
BENCH = H.load_bench()
MS = 1_000_000
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9

# chatglm3-6b, 14 layers: per layer 4096 x (4096 + 2 x 256) + 4096 x 4096
# + 3 x 4096 x 13696 = 203,948,032 weights in products; the head 4096 x 65024
LAYERS = 14 * 203_948_032
HEAD = 266_338_304
#: K and V of one position: 14 layers x 2 KV heads x 128 x 2 sides x 2 bytes
KV_BYTES = 14_336


def _reader(name):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py", "metrics")


def _run(prefill_ns, decode_ns, args=None, trace=True, rehearse=False):
    """A traced run: two prefill and two decode calls in the window, of
    ``prefill_ns`` and ``decode_ns`` each, and the engine's spans with
    ``args`` (by span name; none when absent)."""
    args = args or {}
    modules = {0: [("p(1)", 10 * MS, prefill_ns), ("d(2)", 30 * MS, decode_ns),
                   ("p(1)", 50 * MS, prefill_ns), ("d(2)", 70 * MS, decode_ns),
                   ("p(1)", 110 * MS, 1), ("d(2)", 120 * MS, 1)]}
    bench_spans = [(WINDOW, 0, 100 * MS), ("bench.mark.prefill", 109 * MS, 4 * MS),
                   ("bench.mark.decode", 119 * MS, 4 * MS)]
    spans = [(Event(n, s * MS, 2 * MS), a) for n, s, a in (
        ("pax.serve.prefill", 5, args.get("pax.serve.prefill", [{}, {}])[0]),
        ("pax.serve.decode", 25, args.get("pax.serve.decode", [{}, {}])[0]),
        ("pax.serve.prefill", 45, args.get("pax.serve.prefill", [{}, {}])[1]),
        ("pax.serve.decode", 65, args.get("pax.serve.decode", [{}, {}])[1]),
        ("pax.serve.prefill", 108, {"start": 0, "real": 1, "chunk": 512}))]
    return types.SimpleNamespace(
        cell=H.load_cell(CELL, BENCH), rehearse=rehearse,
        device={"kind": "TPU v5 lite"},
        trace_data=Trace.from_events({0: []}, modules, bench_spans) if trace else None,
        extra={"program_span_args": spans})


PREFILLS = [{"start": 0, "real": 512, "chunk": 512},
            {"start": 1536, "real": 100, "chunk": 512}]
DECODES = [{"rows": 16, "context": 40_000}, {"rows": 10, "context": 20_000}]
# by hand: the layers' products at each real position, attention at each
# position's own context (p + 1 keys: 512 x 513 / 2, and 100 x 1536 +
# 100 x 101 / 2), 4 x 14 x 32 x 128 operations a key, the head once
FLOPS = [2 * LAYERS * 512 + 229_376 * 131_328 + 2 * HEAD,
         2 * LAYERS * 100 + 229_376 * 158_650 + 2 * HEAD]
BYTES = [2 * (LAYERS + HEAD) + KV_BYTES * 40_000,
         2 * (LAYERS + HEAD) + KV_BYTES * 20_000]


def _at(share: float, work: list, peak: float) -> int:
    """Device ns a call that reaches ``share`` of ``peak`` on the mean work."""
    return round(sum(work) / len(work) / (share * peak) * 1e9)


@pytest.mark.parametrize("share", [0.25, 1.0])
def test_known_work_gives_the_known_share(share):
    run = _run(_at(share, FLOPS, PEAK_FLOPS), _at(share, BYTES, PEAK_BYTES),
               {"pax.serve.prefill": PREFILLS, "pax.serve.decode": DECODES})
    assert _reader("prefill_mfu.serve").read(run) == pytest.approx(100 * share, rel=1e-6)
    assert _reader("decode_hbm_share.serve").read(run) == pytest.approx(100 * share, rel=1e-6)


@pytest.mark.parametrize("name", ["prefill_mfu.serve", "decode_hbm_share.serve"])
def test_spans_without_arguments_read_nothing(name):
    assert _reader(name).read(_run(10 * MS, 10 * MS)) is None
    assert _reader(name).read(_run(10 * MS, 10 * MS, trace=False)) is None
    full = {"pax.serve.prefill": PREFILLS, "pax.serve.decode": DECODES}
    assert _reader(name).read(_run(10 * MS, 10 * MS, full, rehearse=True)) is None


def test_the_docqa_8k_cell_keeps_the_file_contract():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    cell = H.load_cell(CELL, BENCH)
    assert entry["chips"] == 1 and entry["traffic"] == "docqa"
    assert cell.spec["engine"] == {"max_batch": 16, "block_size": 16,
                                   "prefill_chunk": 512, "max_seq": 8192}
    assert cell.spec["abi_backend"] == "paxi" and cell.spec["lead_in_s"] >= 20
    assert 0 < cell.spec["check"]["max_logit_gap"] < 1
    assert "rehearsal" in cell.spec and 1 <= len(entry["why"]) <= 200
    config = next(c for c in BENCH["configs"] if c["name"] == "chatglm3-6b")
    assert config["reduced"] == ["num_layers", "layernorm_epsilon", "torch_dtype"]
    e2e = [m["name"] for m in H.metrics_for(BENCH, CELL, trace=False)]
    layer = [m["name"] for m in H.metrics_for(BENCH, CELL, trace=True)]
    assert e2e == ["ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"]
    assert {"prefill_mfu.serve", "decode_hbm_share.serve"} <= set(layer)
    for m in H.metrics_for(BENCH, CELL, trace=True):
        assert m["moves"] in e2e
        assert (H.BENCH / "metrics" / f"{m['name']}.py").exists()


def test_the_docqa_8k_cell_rehearses_on_the_cpu():
    cell = H.load_cell(CELL, BENCH)
    run = H.Run(cell, 2**31 + 23, 6.0, False, True, time.perf_counter())
    H.load_module(H.BENCH / "drivers" / "serve.py", "drivers").run(run)
    line = H.result_line(run, H.reduce_metrics(run, BENCH))
    assert line["correct"] and line["attempted"] > 0 and line["failed"] == 0
    assert {"cpu.ttft_p95_ms", "cpu.itl_p95_ms", "cpu.setup_s"} <= set(line["metrics"])
