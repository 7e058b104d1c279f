"""``correct`` comes out false for each fault a cell can have, and true
without one; the control (the reference at float8) fails a limit.
Runs at the smoke sizes on the CPU, a few minutes in all.

  python -m pytest benchmarks/chip/tests/test_correct.py -q
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import spec  # noqa: E402
TRAIN = "mamba2-370m.hgc11-coded.4chip"
SERVE = "starcoder2-3b-l20.codecomplete.1chip"
SEED = "3000000019"


def rehearse(args, devices: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, *args], env=env, cwd=HERE,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout, out.stderr


@pytest.mark.parametrize("workload,fault,devices", [
    (TRAIN, "none", 4), (TRAIN, "state_unchanged", 4),
    (TRAIN, "half_batch", 4), (TRAIN, "no_exchange", 4),
    (TRAIN, "ssd_state_dropped", 4),
    (SERVE, "none", 1), (SERVE, "frozen_cache", 1),
    (SERVE, "altered_token", 1),
])
def test_fault_is_caught(workload, fault, devices):
    stdout, err = rehearse(["fault_run.py", workload, fault, SEED], devices)
    res = json.loads(stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault == "none"), res
    # every compared number is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail), tail


@pytest.mark.parametrize("workload,devices", [(TRAIN, 4), (SERVE, 1)])
def test_control_fails_a_limit(workload, devices, tmp_path):
    out = tmp_path / "readings.json"
    rehearse([str(HERE.parent / "calibrate.py"), "--workload", workload,
              "--seeds", f"{SEED},3000000021", "--controls", "2",
              "--faults", "0", "--out", str(out), "--rehearse"], devices)
    readings = json.loads(out.read_text())
    limits = spec.load_cell(workload, smoke=True).limits
    for row in readings["program"]:
        assert all(row[k] <= limits[k]["limit"] for k in row
                   if k in limits), row
    for row in readings["control"]:
        assert any(row[k] > limits[k]["limit"] for k in row
                   if k in limits), row
