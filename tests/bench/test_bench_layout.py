"""The benchmark is driven by data: every cell, configuration, traffic mix,
driver, reference and per-layer metric is a file found by name, and
``BENCHMARK.json`` keeps to the contract's shape."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head|"
                   r"expansion|_dim$|_rank$|experts_per_tok)")


def _load(path):
    with open(path) as fh:
        return json.load(fh)


BM = _load(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BM["workloads"]]


def _module(kind, name):
    from bench import run
    return run.load_module(kind, name)


def test_top_level_keys_and_paths():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][1] == "bench/run.py"
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= BM["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines():
    names = ([c["name"] for c in BM["configs"]] + CELLS
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.fullmatch(n), n
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in BM["workloads"]:
        assert NAME.fullmatch(w["traffic"]) and NAME.fullmatch(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file_by_name(cell):
    w = next(x for x in BM["workloads"] if x["name"] == cell)
    cfg = _load(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    assert cfg["name"] == w["config"]
    traffic = _load(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    driver = _module("drivers", traffic["driver"])
    assert callable(driver.run)
    assert callable(_module("reference", cfg["reference"]).replay)
    limits = _load(os.path.join(BENCH, "workloads", f"{cell}.json"))
    assert limits["limits"] and all(
        isinstance(v, (int, float)) for v in limits["limits"].values())
    for m in BM["per_layer"]:
        if cell in m.get("workloads", [cell]):
            assert callable(_module("metrics", m["name"]).read)


def test_configs_list_what_they_reduce():
    for c in BM["configs"]:
        cfg = _load(os.path.join(ROOT, c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not any(WIDTH.search(k) for k in c["reduced"])
        assert any(c["name"] == w["config"] for w in BM["workloads"])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = [m["name"] for m in BM["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in m.get("workloads", [cell])
                   for m in BM["per_layer"]), cell


def test_each_layer_metric_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", [cell]), (
                m["name"], cell)


def test_bounds():
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
