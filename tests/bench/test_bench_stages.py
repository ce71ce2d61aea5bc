"""``bench/stages.py`` and the readers of the preprocessing stages: the
program's stage seconds reach ``partition_s`` and ``expand_s`` through JAX
monitoring events; a program that reports none gives no reading."""
from __future__ import annotations

import pytest
from bench import run as bench_run
from bench import stages

from repro.core import make_synthetic_kg
from repro.training.preprocessing import preprocess_graph

REC = {"counters": {}, "trace": None, "peaks": None, "chips": 1}


@pytest.mark.parametrize("metric, stage", [("partition_s", "partition"),
                                           ("expand_s", "expand")])
def test_reader_gives_the_stage_the_program_reported(metric, stage):
    reader = bench_run.load_module("metrics", metric)
    kg = make_synthetic_kg(200, 6, 1500, seed=3).with_inverse_relations()
    pre = preprocess_graph(kg, num_trainers=2)
    assert reader.read(REC) == pre.seconds[stage] >= 0


@pytest.mark.parametrize("metric", ["partition_s", "expand_s"])
def test_reader_reports_nothing_where_the_program_reports_no_stage(
        metric, monkeypatch):
    monkeypatch.setattr(stages, "seconds", {})
    reader = bench_run.load_module("metrics", metric)
    assert reader.read(REC) is None


def test_other_monitoring_events_are_not_stages(monkeypatch):
    import jax.monitoring
    monkeypatch.setattr(stages, "seconds", {})
    jax.monitoring.record_event_duration_secs("/repro/other/partition", 1.0)
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 2.0)
    assert stages.seconds == {}
