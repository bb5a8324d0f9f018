"""Every cell, configuration, traffic mix and metric named in
BENCHMARK.json has its file under bench/, and the harness finds a new
cell and metric added as new files only."""
import json
import re
import shutil

import pytest

from bench import harness as H

BENCH = H.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_keys_follow_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_names_files_that_exist(w):
    cell = H.load_cell(w["name"], BENCH)
    assert (H.BENCH / "drivers" / f"{cell.kind}.py").exists()
    gen = cell.traffic["generator"]
    assert (H.BENCH / "traffic" / f"{gen}.py").exists()
    assert any(c["name"] == w["config"] for c in BENCH["configs"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = H.metrics_for(BENCH, w["name"], trace=False)
    layer = H.metrics_for(BENCH, w["name"], trace=True)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2 and layer
    for m in e2e + layer:
        assert (H.BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
    for m in layer:       # a per-layer metric's cells report what it moves
        assert m["moves"] in [x["name"] for x in e2e]


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_each_configuration_file_is_its_own(c):
    cfg = H.load_json(H.REPO / c["file"])
    assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    assert c["file"] == f"bench/configs/{c['name']}.json"


def test_peaks_refuse_an_unknown_device_kind():
    assert H.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(H.BenchError, match="no peaks"):
        H.peaks("TPU v99")


def test_a_new_cell_and_metric_are_found_from_new_files_alone(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(H.BENCH, root, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = H.load_json(root / "workloads" / "qwen2-0.5b.serve.chat.json")
    spec["traffic"] = "dummy"
    (root / "workloads" / "qwen2-0.5b.serve.dummy.json").write_text(json.dumps(spec))
    traffic = H.load_json(root / "traffic" / "chat.json")
    (root / "traffic" / "dummy.json").write_text(json.dumps(traffic))
    (root / "metrics" / "dummy_ms.serve.py").write_text(
        "from . import _serve\n\ndef read(run):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "qwen2-0.5b.serve.dummy",
                               "config": "qwen2-0.5b", "traffic": "dummy",
                               "chips": 1, "why": "a test's dummy cell"})
    bench["per_layer"].append({"name": "dummy_ms.serve", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "scheduler", "moves": "ttft_p95_ms",
                               "workloads": ["qwen2-0.5b.serve.dummy"]})
    cell = H.load_cell("qwen2-0.5b.serve.dummy", bench, root)
    assert cell.traffic == traffic
    names = [m["name"] for m in H.metrics_for(bench, cell.name, trace=True)]
    assert names == ["dummy_ms.serve"]
    run = H.Run(cell, 1, 1.0, True, False, 0.0)
    assert H.reduce_metrics(run, bench) == {"dummy_ms.serve": {"value": 42.0, "unit": "ms"}}
