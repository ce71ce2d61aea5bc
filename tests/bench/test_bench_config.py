"""``bench/drivers/train.py`` builds the trainer from every setting a
configuration file states: the ``TrainConfig`` of each configuration is the
one its keys name, a field added to a file reaches the trainer with no edit
to the driver, and a key that is neither a field nor the harness's own is
an error before any work."""
from __future__ import annotations

import json
import os
import types

import pytest
from bench import harness
from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BM = json.load(fh)
TRAIN_CONFIGS = sorted({w["config"] for w in BM["workloads"]
                        if w["traffic"] == "epochs"})


def _cfg(name, rehearse=False):
    cfg = bench_run.load_json(os.path.join(ROOT, "bench", "configs",
                                           f"{name}.json"))
    return bench_run.apply_rehearsal(cfg) if rehearse else cfg


def _driver():
    return bench_run.load_module("drivers", "train")


def _thirteen_keys(cfg):
    """The ``TrainConfig`` as the driver built it from its 13 named keys
    before it took every field a configuration states."""
    from repro.training import TrainConfig
    return TrainConfig(
        num_trainers=cfg["num_trainers"], strategy=cfg["strategy"],
        num_hops=cfg["num_hops"], hidden_dim=cfg["hidden_dim"],
        num_bases=cfg["num_bases"], num_negatives=cfg["num_negatives"],
        negative_sampler=cfg["negative_sampler"],
        batch_size=cfg["batch_size"], learning_rate=cfg["learning_rate"],
        dropout=cfg["dropout"], decoder=cfg["decoder"],
        table_dtype=cfg["table_dtype"],
        num_table_shards=cfg["num_table_shards"],
        seed=int(cfg["dataset"]["seed"]), epochs=1)


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", TRAIN_CONFIGS)
def test_train_config_equals_the_one_the_thirteen_keys_built(name, rehearse):
    cfg = _cfg(name, rehearse)
    assert _driver().train_config(cfg) == _thirteen_keys(cfg)


@pytest.mark.parametrize("key,value", [("use_kernel", True),
                                       ("prefetch", 5)])
def test_a_field_in_the_configuration_reaches_the_trainer(key, value):
    from repro.training import TrainConfig
    assert getattr(TrainConfig(), key) != value
    cfg = {**_cfg("rgcn-fb15k237", rehearse=True), key: value}
    _, trainer, _ = _driver().build_trainer(types.SimpleNamespace(cfg=cfg))
    try:
        assert getattr(trainer.cfg, key) == value
    finally:
        trainer.close()


@pytest.mark.parametrize("key", ["hiden_dim", "seed", "epochs"])
def test_an_unknown_or_harness_set_key_is_an_error_before_any_work(
        monkeypatch, key):
    drv = _driver()

    def no_work(cfg):
        raise AssertionError("the dataset was built")

    monkeypatch.setattr(drv.graphs, "make_dataset", no_work)
    cfg = {**_cfg("rgcn-fb15k237", rehearse=True), key: 7}
    with pytest.raises(harness.Error, match=key):
        drv.build_trainer(types.SimpleNamespace(cfg=cfg))


def test_the_step_lowered_after_the_window_is_the_step_that_ran():
    """A traced run reads the scopes of the step lowered for the last batch
    the window fed it; that compiles to the program the pipeline's own
    batch compiles to, instruction for instruction."""
    drv = _driver()
    _, trainer, _ = drv.build_trainer(types.SimpleNamespace(
        cfg=_cfg("rgcn-fb15k237", rehearse=True)))
    try:
        probe = harness.StepProbe(trainer, None)
        trainer.train_epoch()
        probe.detach()
        assert probe.last_batch is not None
        ran = trainer.lower_step(probe.last_batch).compile().as_text()
        own = trainer.lower_step().compile().as_text()
        assert ran == own
    finally:
        trainer.close()
