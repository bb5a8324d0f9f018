"""The training check on four CPU devices at the rehearsal size: a sound
run is correct, and its control (the float32 reference at float8) is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def train_case(*args) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(HERE / "train_case.py"), *args],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_the_control_is_not():
    r = train_case("--control")
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    limits = {k: lim for k, (_, lim) in r["checks"].items()}
    assert any(v > limits[k] for k, v in r["control"].items())
