"""A run with its timed path broken underneath comes out not correct.

Each test drives the rest of a run (``--rehearse`` skips the look for a
chip) with one fault planted in the program: a training step that returns
the state it was given, and a step that leaves out half of every
trainer's rows and takes the mean over the rest.  (The exchange between
chips exists in no cell.)"""
from __future__ import annotations

import json
import os

import pytest
from bench import control
from bench import run as bench_run


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    TRAIN_CELLS = [w["name"] for w in json.load(fh)["workloads"]
                   if w["traffic"] == "epochs"]


def _run(capsys, cell):
    rc = bench_run.main(["--workload", cell, "--seed", "9", "--seconds",
                         "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _broken_steps(monkeypatch, fault):
    import repro.training.trainer as trainer_mod
    real_factory = trainer_mod.make_simulated_train_step

    def factory(loss, optimizer, *, donate_batch=False):
        real = real_factory(loss, optimizer, donate_batch=donate_batch)

        def step(params, opt_state, batch, keys):
            if fault == "unchanged":
                return params, opt_state, real(params, opt_state, batch,
                                               keys)[2]
            full = "core_edge_mask" in batch
            return real(params, opt_state, control.half_rows(batch, full),
                        keys)
        return step

    monkeypatch.setattr(trainer_mod, "make_simulated_train_step", factory)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_broken_training_step_is_not_correct(capsys, monkeypatch, cell,
                                             fault):
    _broken_steps(monkeypatch, fault)
    line = _run(capsys, cell)
    assert line["correct"] is False, line["checks"]


def test_unbroken_training_step_is_correct(capsys):
    assert _run(capsys, "fb15k237.train.full")["correct"] is True

