"""The benchmark harness: finds a cell and everything it names by name,
checks the devices, runs the cell's driver, reduces metrics and prints the
result line.

Everything that belongs to one cell, configuration, traffic mix or metric
sits in a file of its own under ``bench/``:

* ``workloads/<cell>.json``  — config, chips, kind, engine settings, rate,
  traffic name, the numbers ``correct`` compares with their limits, why;
* ``configs/<config>.json``  — the published sizes as run, ``reduced``,
  ``assumed``, ``source``, the deployment;
* ``traffic/<traffic>.json`` — parameters for the generator it names,
  ``traffic/<generator>.py``;
* ``metrics/<metric>.py``    — ``read(run) -> float | None``;
* ``drivers/<kind>.py``      — ``run(run)``: set-up, window, check.

``BENCHMARK.json`` at the repository root says which metrics a cell
reports: its ``end_to_end`` metrics without ``--trace``, its ``per_layer``
metrics with it (a metric with a ``workloads`` list only in those cells).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


class BenchError(RuntimeError):
    """A cell that cannot run here: missing files, wrong devices."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, package: str):
    """Import the file ``path`` as a module of ``bench.<package>`` (its
    relative imports then work); dots in the file's stem become ``__``."""
    name = f"bench.{package}.{path.stem.replace('.', '__')}"
    if name in sys.modules:
        return sys.modules[name]
    if not path.exists():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload entry of ``BENCHMARK.json`` with its files loaded."""
    name: str
    entry: dict          # the BENCHMARK.json workloads entry
    spec: dict           # workloads/<cell>.json
    config: dict         # configs/<config>.json
    traffic: dict        # traffic/<traffic>.json
    root: Path

    @property
    def kind(self) -> str:
        return self.spec["kind"]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def load_bench(repo: Path = REPO) -> dict:
    return load_json(repo / "BENCHMARK.json")


def load_cell(name: str, bench: dict, root: Path = BENCH) -> Cell:
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(entries)})")
    entry = entries[name]
    spec = load_json(root / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise BenchError(f"{name}: {key} is {spec[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    config = load_json(root / "configs" / f"{spec['config']}.json")
    traffic = load_json(root / "traffic" / f"{spec['traffic']}.json")
    return Cell(name, entry, spec, config, traffic, root)


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``cell`` reports."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peaks(kind: str, root: Path = BENCH) -> dict:
    """The chip's peaks by JAX's ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(root / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json "
                         f"(have {sorted(table)})")
    return table[kind]


class Spans:
    """Host spans of the harness around the program's calls: ``(name,
    start, end)`` on ``time.perf_counter``, and, while the profiler runs,
    a ``TraceAnnotation`` of the same name on the trace's clock."""

    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        def wrapped(*a, **kw):
            with self(name):
                return fn(*a, **kw)
        return wrapped

    def durations(self, name: str, t0: float = -math.inf,
                  t1: float = math.inf) -> list[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= t0 and e <= t1]


@dataclasses.dataclass
class Run:
    """What one run hands the metric readers."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float                       # process start (perf_counter)
    spans: Spans = dataclasses.field(default_factory=Spans)
    setup_s: Optional[float] = None
    window: Optional[tuple[float, float]] = None
    attempted: int = 0
    failed: int = 0
    # driver-specific records (requests, steps) and counters
    records: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    checks: dict = dataclasses.field(default_factory=dict)   # name -> (value, limit)
    trace_data: Any = None               # metrics/_trace.Trace, traced runs
    device: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)    # earlier-line facts

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v is not None and math.isfinite(v) and v <= lim
            for v, lim in self.checks.values())


def require_devices(chips: int, rehearse: bool):
    """The devices the cell runs on; raises without a TPU (unless
    rehearsing on the CPU) or with a device count other than ``chips``."""
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), with every program kept, however quick
    its compile, so a warm run compiles nothing."""
    import jax
    from repro.launch.device import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


#: where a traced run's profile goes (inside the checkout)
TRACE_DIR = REPO / ".bench_trace"


def start_trace(run: Run) -> None:
    """Start the profiler (Python tracer off) and the span that brackets
    the traced window; the harness's spans become ``TraceAnnotation``s."""
    import shutil

    import jax
    from .metrics import _trace
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    run.spans.annotate = True
    run.extra["trace_cm"] = jax.profiler.TraceAnnotation(_trace.WINDOW)
    run.extra["trace_cm"].__enter__()


def stop_trace(run: Run, after_window=None) -> None:
    """Close the traced window, run ``after_window`` (still traced), stop
    the profiler and read the trace into ``run``."""
    import jax
    from .metrics import _trace
    run.extra.pop("trace_cm").__exit__(None, None, None)
    if after_window is not None:
        after_window()
    jax.profiler.stop_trace()
    run.spans.annotate = False
    run.trace_data = _trace.Trace.from_dir(TRACE_DIR)
    run.device["busy_s"] = run.trace_data.busy_s()
    run.device["window_s"] = run.trace_data.window_s()


def reduce_metrics(run: Run, bench: dict) -> dict:
    out = {}
    for m in metrics_for(bench, run.cell.name, run.trace):
        reader = load_module(run.cell.root / "metrics" / f"{m['name']}.py",
                             "metrics")
        value = reader.read(run)
        if value is None:
            continue
        name = f"cpu.{m['name']}" if run.rehearse else m["name"]
        out[name] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, metrics: dict) -> dict:
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": run.device}
    if run.trace and run.trace_data is not None:
        line["breakdown"] = run.trace_data.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run.checks.items()}
    return line


def print_checks(run: Run) -> None:
    for k, (v, lim) in run.checks.items():
        verdict = "ok" if v is not None and v <= lim else "FAIL"
        print(f"check {k} = {v!r} limit {lim!r} {verdict}", file=sys.stderr)
    print(f"correct = {run.correct}", file=sys.stderr, flush=True)
