"""The control, the plain reference computed in bfloat16 in the program's
place, comes out not correct under each cell's limits, and so does every
planted fault; the program itself comes out correct (CPU, tiny sizes)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    CELLS = [w["name"] for w in json.load(fh)["workloads"]]


def _fails(readings, limits):
    return any(readings[k] > v for k, v in limits.items())


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_fail_the_limits(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "control.py"),
         "--workload", cell, "--seeds", "21", "--seconds", "2",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(os.path.join(ROOT, "bench", "workloads", f"{cell}.json")) as fh:
        limits = json.load(fh)["limits"]
    assert not _fails(out["program"], limits), out["program"]
    assert _fails(out["control"], limits), out["control"]
    assert out["faults"]
    for name, readings in out["faults"].items():
        assert _fails(readings, limits), (name, readings)
