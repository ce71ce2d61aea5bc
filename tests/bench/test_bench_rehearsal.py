"""A ``--rehearse`` run of each cell, on the CPU at a tiny size, prints a
last line of the contract's shape, names the CPU, and is correct."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BM = json.load(fh)


def rehearse(cell: str, trace: int, seed: int = 4294967311):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]
                                  if w["chips"] == 1])
def test_rehearsal_prints_the_contract_line(cell):
    line, err = rehearse(cell, trace=0)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 1
    want = {m["name"] for m in BM["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_traced_rehearsal_reports_layers_and_the_window():
    cell = BM["workloads"][0]["name"]
    line, _ = rehearse(cell, trace=1, seed=5)
    dev = line["device"]
    assert dev["window_s"] > 0 and dev["busy_s"] >= 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    names = [n for n, _ in line["breakdown"]["idle_gaps"]]
    # the gaps are named by the program's own spans
    assert "train.epoch" in names and "train.wait" in names
    # host preprocessing is a host clock; device metrics are never
    # reported from a CPU run
    assert line["metrics"]["host_prep_s"]["value"] > 0
    for m in line["metrics"]:
        assert not m.startswith(("idle_share", "mfu"))
    # the step's scopes are device metrics of the cell, left out here
    scoped = {"gather_ms.train", "message_ms.train", "aggregate_ms.train",
              "decoder_loss_ms.train", "optimizer_ms.train",
              "unscoped_share.train"}
    assert scoped <= {m["name"] for m in BM["per_layer"]
                      if cell in m.get("workloads", [cell])}
    assert not scoped & set(line["metrics"])
