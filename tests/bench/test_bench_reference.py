"""``bench/reference/rgcn.py``'s full-graph loss weights each negative by
its own edge's core mask (row ``e * s + j`` corrupts edge ``e``), as the
program does: at one negative per edge the loss is bitwise what tiling the
mask over rows gave, and at two it is the program's ``fullgraph_loss`` on a
seeded toy graph, where tiling is not."""
from __future__ import annotations

import pytest
from bench import run as bench_run

HIDDEN, DROPOUT = 16, 0.2


def _tiled(core, num_negatives):
    import jax.numpy as jnp
    return jnp.concatenate([core] * (1 + num_negatives))


def _losses(monkeypatch, num_negatives):
    """Per trainer: the program's loss, the reference's, and the
    reference's with the mask tiled over rows."""
    import jax
    from repro.data import synthetic_fb15k
    from repro.training import KGETrainer, TrainConfig

    ref = bench_run.load_module("reference", "rgcn")
    trainer = KGETrainer(
        synthetic_fb15k(scale=0.01, seed=0),
        TrainConfig(num_trainers=2, epochs=1, hidden_dim=HIDDEN,
                    batch_size=None, num_negatives=num_negatives,
                    dropout=DROPOUT))
    try:
        kg = trainer.train_kg
        params = ref.init_params(jax.random.PRNGKey(3), kg.num_entities,
                                 kg.num_relations, HIDDEN, 2, 2, None)
        _, _, batch, keys = trainer.next_step_args()
        out = []
        for i in range(keys.shape[0]):
            part = {k: v[i] for k, v in batch.items()}
            prog = trainer._fullgraph_loss(params, part, keys[i])[0]
            mine = ref.fullgraph_loss(params, None, part, keys[i],
                                      num_negatives, DROPOUT)
            with monkeypatch.context() as m:
                m.setattr(ref, "triplet_weights", _tiled)
                tiled = ref.fullgraph_loss(params, None, part, keys[i],
                                           num_negatives, DROPOUT)
            out.append((float(prog), float(mine), float(tiled)))
        return out
    finally:
        trainer.close()


def test_one_negative_is_bitwise_the_tiled_mask(monkeypatch):
    for _, mine, tiled in _losses(monkeypatch, 1):
        assert mine == tiled


def test_two_negatives_match_the_program_and_tiling_does_not(monkeypatch):
    for prog, mine, tiled in _losses(monkeypatch, 2):
        assert mine == pytest.approx(prog, rel=1e-6)
        assert abs(tiled - prog) > 1e-5 * abs(prog)


def test_triplet_weights_follow_each_negative_to_its_edge():
    import jax.numpy as jnp
    import numpy as np
    ref = bench_run.load_module("reference", "rgcn")
    w = ref.triplet_weights(jnp.array([1.0, 0.0, 1.0]), 2)
    np.testing.assert_array_equal(w, [1, 0, 1, 1, 1, 0, 0, 1, 1])
