"""Every cell, configuration, traffic mix and metric named in
BENCHMARK.json has its file under bench/, and the harness finds a new
cell, metric and model family added as new files only."""
import json
import re
import shutil
import time

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


def _with_family(tmp_path, module: str, cells: list[str]):
    """A copy of bench/ with a configuration ``twin`` (qwen2-0.5b's file
    naming ``module``) and a twin of each of ``cells`` on it, added as new
    files, and BENCHMARK.json's entries for them; returns ``(root,
    bench, twins, files)``, ``files`` those the copy had before."""
    root = tmp_path / "bench"
    shutil.copytree(H.BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    files = sorted(p.relative_to(root) for p in root.rglob("*") if p.is_file())
    config = H.load_json(root / "configs" / "qwen2-0.5b.json")
    config["reference"]["module"] = module
    (root / "configs" / "twin.json").write_text(json.dumps(config))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "twin", "source": config["source"],
                             "file": "bench/configs/twin.json", "reduced": [],
                             "why": "the qwen2-0.5b file under a new family"})
    twins = []
    for name in cells:
        spec = H.load_json(root / "workloads" / f"{name}.json")
        twin = name.replace("qwen2-0.5b", "twin")
        spec["config"] = "twin"
        (root / "workloads" / f"{twin}.json").write_text(json.dumps(spec))
        bench["workloads"].append({"name": twin, "config": "twin",
                                   "traffic": spec["traffic"],
                                   "chips": spec["chips"], "why": "a twin"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if name in m.get("workloads", []):
                m["workloads"].append(twin)
        twins.append(twin)
    return root, bench, twins, files


def test_a_new_family_needs_new_files_only(tmp_path):
    root, bench, (twin,), files = _with_family(
        tmp_path, "dense_twin", ["qwen2-0.5b.serve.chat"])
    (root / "reference" / "dense_twin.py").write_text(
        '"""The dense family under another name."""\n'
        "from .dense import *  # noqa: F401,F403\n")
    cell = H.load_cell(twin, bench, root)
    assert H.family(cell).__name__ == "bench.reference.dense_twin"
    run = H.Run(cell, 2**31 + 5, 6.0, False, True, time.perf_counter())
    H.load_module(H.BENCH / "drivers" / "serve.py", "drivers").run(run)
    line = H.result_line(run, H.reduce_metrics(run, bench))
    assert line["correct"] and line["attempted"] > 0 and line["failed"] == 0
    assert {"cpu.ttft_p95_ms", "cpu.setup_s"} <= set(line["metrics"])
    for f in files:
        assert (root / f).read_bytes() == (H.BENCH / f).read_bytes(), f


@pytest.mark.parametrize("name", ["qwen2-0.5b.serve.chat",
                                  "qwen2-0.5b.train.zero1-dp4"])
def test_an_unknown_family_module_is_refused_naming_its_file(tmp_path, name):
    _, bench, (twin,), _ = _with_family(tmp_path, "no_such_family", [name])
    cell = H.load_cell(twin, bench, tmp_path / "bench")
    run = H.Run(cell, 1, 1.0, False, True, time.perf_counter())
    driver = H.load_module(H.BENCH / "drivers" / f"{cell.kind}.py", "drivers")
    with pytest.raises(H.BenchError, match=r"reference/no_such_family\.py"):
        driver.run(run)
