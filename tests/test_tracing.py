"""The program's own trace: named scopes on each layer of the compiled train
step, host spans around ``KGETrainer.train_epoch``'s work, and
preprocessing's stage seconds.

A scope only adds HLO ``op_name`` metadata; the sim/spmd bitwise gates in
``test_distributed.py`` hold the values to that."""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time

import jax
import pytest

from repro.core import make_synthetic_kg
from repro.data import synthetic_fb15k
from repro.training import KGETrainer, TrainConfig
from repro.training.preprocessing import preprocess_graph

SCOPES = ("kge.gather", "kge.message", "kge.aggregate", "kge.decoder_loss",
          "kge.optimizer")
HEAVY = ("gather", "scatter", "dot", "reduce")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')

STEPS = {
    "full": dict(batch_size=None),
    "minibatch": dict(batch_size=256),
    "spmd": dict(batch_size=None, spmd=True),     # shard_map on a 1x1 mesh
}


def _trainer(**kw) -> KGETrainer:
    splits = synthetic_fb15k(scale=0.01, seed=0)
    return KGETrainer(splits, TrainConfig(num_trainers=2, epochs=1,
                                          hidden_dim=16, **kw))


@pytest.fixture(scope="module", params=sorted(STEPS))
def step_hlo(request):
    """``(op_name, opcode)`` of every instruction of a compiled step."""
    text = _trainer(**STEPS[request.param]).lower_step().compile().as_text()
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            name = OP_NAME.search(line)
            out.append((name.group(1) if name else "", m.group(1)))
    return out


def test_step_hlo_names_every_layer(step_hlo):
    names = [n for n, _ in step_hlo]
    for scope in SCOPES:
        assert any(scope in n for n in names), scope
    for scope in SCOPES[:4]:       # the model's layers, under jax.grad
        assert any(scope in n and "transpose(" in n for n in names), scope


def test_step_heavy_ops_all_lie_under_a_scope(step_hlo):
    heavy = [(n, op) for n, op in step_hlo if op in HEAVY]
    assert heavy
    loose = [(n, op) for n, op in heavy if "kge." not in n]
    assert not loose, loose[:5]


@pytest.fixture(scope="module")
def epoch_spans():
    """Host events ``(name, start, end, step_num)`` of one traced epoch of a
    mini-batch trainer (several steps, each with its own key fold-in)."""
    from jax.profiler import ProfileData
    tr = _trainer(batch_size=256)
    tr.train_epoch()                    # compiles outside the trace
    d = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(d):
            rec = tr.train_epoch()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        profile = ProfileData.from_file(path)
    finally:
        shutil.rmtree(d, ignore_errors=True)
        tr.close()
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train.", "pipeline.")):
                    stats = dict(e.stats)
                    spans.append((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  stats.get("step_num")))
    return spans, rec


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_train_epoch_spans_nest_as_documented(epoch_spans):
    spans, rec = epoch_spans
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (epoch,) = by["train.epoch"]
    steps = by["train.step"]
    n = rec["num_batches"]
    assert n >= 2 and len(steps) == n
    assert [s[3] for s in steps] == list(range(steps[0][3],
                                               steps[0][3] + n))
    assert all(_inside(s, epoch) for s in spans)
    for name in ("train.dispatch", "train.wait"):
        assert len(by[name]) == n
        assert all(any(_inside(x, s) for s in steps) for x in by[name])
    # a fetch before each step, one more that finds the epoch ended
    fetches = by["pipeline.next_batch"]
    assert len(fetches) == n + 1
    assert not any(_inside(f, s) for f in fetches for s in steps)
    # the epoch's key split, and each step's fold-in inside its step
    keys = by["train.keys"]
    assert len(keys) == n + 1
    assert sum(any(_inside(k, s) for s in steps) for k in keys) == n
    for s in steps:
        inner = sorted((x for x in spans if x is not s and _inside(x, s)),
                       key=lambda x: x[1])
        assert [x[0] for x in inner] == ["train.keys", "train.dispatch",
                                        "train.wait"]


@pytest.mark.parametrize("batch_size", [None, 128])
def test_preprocess_stage_seconds(batch_size):
    kg = make_synthetic_kg(300, 10, 2500, seed=7).with_inverse_relations()
    t0 = time.perf_counter()
    pre = preprocess_graph(kg, num_trainers=2, batch_size=batch_size)
    wall = time.perf_counter() - t0
    want = {"partition", "expand", "pad"}
    if batch_size is not None:
        want.add("budgets")
    assert set(pre.seconds) == want
    assert all(v >= 0 for v in pre.seconds.values())
    assert sum(pre.seconds.values()) <= wall


def test_preprocess_stage_events_reach_monitoring_listeners():
    heard = {}

    def listen(event, secs, **_):
        if event.startswith("/repro/setup/"):
            heard[event.rsplit("/", 1)[1]] = secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        kg = make_synthetic_kg(200, 6, 1500, seed=3).with_inverse_relations()
        pre = preprocess_graph(kg, num_trainers=2)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert heard == pre.seconds


def test_trainer_reports_its_setup_stages():
    heard = {}

    def listen(event, secs, **_):
        if event.startswith("/repro/setup/"):
            heard[event.rsplit("/", 1)[1]] = secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        tr = _trainer(batch_size=None)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert set(heard) == set(tr.pre.seconds) | {"init"}
    assert heard["init"] > 0


@pytest.mark.parametrize("batch_size", [None, 256])
def test_trainer_records_core_slot_fill(batch_size):
    """A full-graph trainer records, once at set-up, the share of its
    decoder's rows that are real core edges; a mini-batch one does not."""
    from repro.training.trainer import CORE_SLOT_FILL_EVENT
    heard = []

    def listen(event, value, **_):
        if event == CORE_SLOT_FILL_EVENT:
            heard.append(value)

    jax.monitoring.register_scalar_listener(listen)
    try:
        tr = _trainer(batch_size=batch_size)
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    if batch_size is None:
        assert heard == [tr.padded.core_slot_fill()]
        assert 0.0 < heard[0] <= 1.0
    else:
        assert heard == []
