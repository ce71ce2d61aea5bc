"""``chip_smoke.py``: its CPU rehearsal runs end to end, and it refuses to
report a result without a TPU or without the rest of the repository."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, cwd=ROOT, script=SCRIPT, timeout=180, drop=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for key in drop:
        env.pop(key, None)
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_rehearsal_runs_every_phase_and_reports_its_platform():
    proc = _run("--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = _last_json(proc.stdout)
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = [json.loads(line) for line in proc.stdout.splitlines()[:-1]]
    by_name = {p["phase"]: p for p in phases}
    assert set(by_name) == {"data", "a_fullgraph_train",
                            "b_minibatch_int8_2shard_train", "c_eval",
                            "d_serve"}
    for name, rec in by_name.items():
        assert name == "data" or rec["ok"], rec
    assert by_name["b_minibatch_int8_2shard_train"]["shapes"][
        "entity_table"] == [2, 100, 75]
    assert [r["shards"] for r in by_name["d_serve"]["runs"]] == [1, 2]


def test_four_device_rehearsal_matches_the_simulated_step_bitwise():
    """``--chips 4`` on four forced CPU devices: the shard_map trainer on
    a 2x2 data x model mesh against the simulated step, losses and
    parameters bitwise equal."""
    proc = _run("--rehearse", "--chips", "4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert _last_json(proc.stdout) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    rec = json.loads(proc.stdout.splitlines()[-2])
    assert rec["phase"] == "spmd_vs_sim"
    assert rec["shapes"]["mesh"] == {"data": 2, "model": 2}
    assert rec["max_loss_diff"] == 0.0 and rec["max_param_diff"] == 0.0


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc = _run()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run("--rehearse", cwd=tmp_path, script=str(alone),
                drop=("PYTHONPATH",))
    assert proc.returncode != 0
    last = _last_json(proc.stdout)
    assert not (isinstance(last, dict) and last.get("ok"))
