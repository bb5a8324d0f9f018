"""The serving check on the CPU at the rehearsal size: a sound run is
correct, and each fault a serving cell can have, planted under the timed
path, comes out not correct."""
import time

import numpy as np
import pytest

from bench import harness as H
from bench.faults import FAULTS

CELL = "qwen2-0.5b.serve.chat"
SECONDS = 6.0


def serve(seed, control=False):
    bench = H.load_bench()
    cell = H.load_cell(CELL, bench)
    run = H.Run(cell, seed, SECONDS, False, True, time.perf_counter())
    run.extra["control"] = control
    H.load_module(H.BENCH / "drivers" / "serve.py", "drivers").run(run)
    return run


def gap(run):
    value, limit = run.checks["max_logit_gap"]
    assert np.isfinite(value), "no finished request was checked"
    return value, limit


def test_sound_run_is_correct_and_the_control_is_not():
    run = serve(2**31 + 3, control=True)
    value, limit = gap(run)
    assert run.correct and value <= limit
    assert run.extra["control_gaps"]["max_logit_gap"] > limit
    assert run.attempted > 0 and run.failed == 0


@pytest.mark.parametrize("fault", ["token-altered", "decode-state-unchanged",
                                   "top-k-ignored", "sample-key-wrong"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    from repro.models import transformer
    from repro.serve.engine import ServeEngine
    # the planters patch the program; undo them after the test
    monkeypatch.setattr(ServeEngine, "_sample_one", ServeEngine._sample_one)
    monkeypatch.setattr(ServeEngine, "_req_key", ServeEngine._req_key)
    monkeypatch.setattr(transformer, "decode_step_paged",
                        transformer.decode_step_paged)
    FAULTS[fault]()
    run = serve(2**31 + 3)
    value, limit = gap(run)
    assert value > limit and not run.correct
