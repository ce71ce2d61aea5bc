"""The benchmark's datasets hold exactly the published split sizes times
the configuration's scale, all distinct, and are the same on every call."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest
from bench import graphs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    CONFIGS = [c["file"] for c in json.load(fh)["configs"]]


def _cfg(path, scale):
    with open(os.path.join(ROOT, path)) as fh:
        cfg = json.load(fh)
    return {**cfg, "scale": scale}


@pytest.mark.parametrize("path", CONFIGS)
def test_splits_hold_exactly_the_published_counts_times_scale(path):
    cfg = _cfg(path, 0.02)
    data = graphs.make_dataset(cfg)
    pub = cfg["dataset"]
    for name in ("train", "valid", "test"):
        want = max(pub[f"min_{name}"], round(pub[f"published_{name}"] * 0.02))
        assert len(data["splits"][name]) == want, name
    trip = np.concatenate(list(data["splits"].values()))
    assert len(np.unique(trip, axis=0)) == len(trip)
    assert not (trip[:, 0] == trip[:, 2]).any()
    assert trip[:, [0, 2]].max() < data["num_entities"]
    assert trip[:, 1].max() < data["num_relations"]


def test_same_seed_same_graph_other_seed_other_graph():
    cfg = _cfg(CONFIGS[0], 0.01)
    a, b = graphs.make_dataset(cfg), graphs.make_dataset(cfg)
    for name in a["splits"]:
        np.testing.assert_array_equal(a["splits"][name], b["splits"][name])
    other = {**cfg, "dataset": {**cfg["dataset"], "seed": 1}}
    c = graphs.make_dataset(other)
    assert not np.array_equal(a["splits"]["train"], c["splits"]["train"])


def test_duplicate_draws_are_redrawn():
    # 40 entities and one relation hold 1,560 distinct triplets; Zipf draws
    # repeat often, and the generator still returns the count asked for
    rng = np.random.default_rng(0)
    trip = graphs.distinct_triplets(40, 1, 1200, rng)
    assert len(np.unique(trip, axis=0)) == 1200

