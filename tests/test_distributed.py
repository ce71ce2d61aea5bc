"""Distributed-training equivalence (paper §2.2, §4.5.1).

The paper's correctness claim: gradient-sharing BEFORE the optimizer step
makes distributed training mathematically equivalent to non-distributed
training on the union of the data.  We verify:

1. vmap+mean gradient == mean of per-trainer grads computed separately;
2. the simulated-trainer step with P=1 == a plain single-step update;
3. end-to-end: distributed (4 trainers) reaches the same loss region and
   comparable eval metrics as 1 trainer (Table 3's structure).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    expand_all, pad_partitions, partition_graph,
)
from repro.data import synthetic_fb15k
from repro.models import KGEConfig, RGCNConfig, fullgraph_loss, \
    init_kge_params
from repro.training import (
    KGETrainer, TrainConfig, adam, make_simulated_train_step,
)


def _setup(small_kg, p):
    parts = partition_graph(small_kg, p, "vertex_cut", seed=0)
    exp = expand_all(small_kg, parts, 2)
    pb = pad_partitions(exp)
    cfg = KGEConfig(rgcn=RGCNConfig(
        num_entities=small_kg.num_entities,
        num_relations=small_kg.num_relations,
        hidden_dim=16, num_layers=2, num_bases=2, dropout=0.0))
    params = init_kge_params(jax.random.PRNGKey(0), cfg)
    batch = {f.name: jnp.asarray(getattr(pb, f.name))
             for f in dataclasses.fields(pb)}
    return cfg, params, batch


def test_grad_average_equals_per_trainer_mean(small_kg):
    cfg, params, batch = _setup(small_kg, 4)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)

    def loss_one(p, b, k):
        return fullgraph_loss(p, cfg, b, k, train=False)

    # per-trainer grads, averaged by hand
    gs = []
    for i in range(4):
        b_i = jax.tree_util.tree_map(lambda x: x[i], batch)
        g = jax.grad(lambda p: loss_one(p, b_i, keys[i])[0])(params)
        gs.append(g)
    manual = jax.tree_util.tree_map(
        lambda *x: sum(x) / 4.0, *gs)

    # vmapped (the simulated AllReduce path)
    def grad_one(p, b, k):
        return jax.grad(lambda q: loss_one(q, b, k)[0])(p)
    vg = jax.vmap(grad_one, in_axes=(None, 0, 0))(params, batch, keys)
    auto = jax.tree_util.tree_map(lambda g: jnp.mean(g, 0), vg)

    for a, b in zip(jax.tree_util.tree_leaves(manual),
                    jax.tree_util.tree_leaves(auto)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_single_trainer_step_equals_plain_step(small_kg):
    cfg, params, batch = _setup(small_kg, 1)
    opt = adam(0.01)
    opt_state = opt.init(params)
    key = jax.random.PRNGKey(2)

    def loss_one(p, b, k):
        return fullgraph_loss(p, cfg, b, k, train=False)

    step = make_simulated_train_step(loss_one, opt)
    p_dist, _, m = step(params, opt_state, batch,
                        key[None].repeat(1, axis=0)
                        if key.ndim else jnp.stack([key]))

    # plain non-distributed update
    b0 = jax.tree_util.tree_map(lambda x: x[0], batch)
    (loss, _), g = jax.value_and_grad(
        lambda p: loss_one(p, b0, key), has_aux=True)(params)
    upd, _ = opt.update(g, opt.init(params), params)
    p_plain = jax.tree_util.tree_map(lambda p, u: p + u, params, upd)

    assert float(m["loss"]) == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_dist),
                    jax.tree_util.tree_leaves(p_plain)):
        # jit-fused vs eager reduction order: tolerate ~1e-4 relative
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


# ====================================================================== #
# The full-graph decoder scores only the core edges (core_edge_index)
# ====================================================================== #
def _masked_fullgraph_loss(params, cfg, part, rng, train):
    """The all-edge formulation: every local edge and its negatives
    scored, each row weighted by its edge's core mask (s = 1, where the
    mask tiles over the negatives edge for edge)."""
    from repro.core.negative import (
        constraint_based_negatives, global_closed_world_negatives,
        mix_pos_neg,
    )
    from repro.models import decoders
    from repro.models.kge import vertex_input
    from repro.models.rgcn import rgcn_encode
    assert cfg.num_negatives == 1
    k_neg, k_drop = jax.random.split(rng)
    x = vertex_input(params, cfg, part["local_to_global"], None)
    x = jnp.where(part["vertex_mask"][:, None], x, 0.0)
    h = rgcn_encode(params, cfg.rgcn, x, part["src"], part["rel"],
                    part["dst"], part["edge_mask"],
                    dropout_key=k_drop if train else None, train=train)
    pos = jnp.stack([part["src"], part["rel"], part["dst"]], axis=1)
    if cfg.negative_sampler == "global":
        neg, _ = global_closed_world_negatives(
            k_neg, pos, 1, int(part["local_to_global"].shape[0]))
    else:
        neg, _ = constraint_based_negatives(
            k_neg, pos, 1, part["num_core_vertices"])
    trip, labels = mix_pos_neg(pos, neg)
    core = part["core_edge_mask"].astype(jnp.float32)
    scores = decoders.score_triplets(params["decoder"], cfg.decoder, h, trip)
    return decoders.bce_loss(scores, labels, jnp.concatenate([core, core]))


@pytest.mark.parametrize("train", [False, True])
@pytest.mark.parametrize("sampler", ["constraint", "global"])
@pytest.mark.parametrize("decoder", ["distmult", "transe"])
def test_fullgraph_loss_core_rows_equal_masked_all_edges(
        small_kg, decoder, sampler, train):
    """Scoring the compacted core rows gives the masked all-edge loss and
    gradient: the support rows it leaves out weigh exactly zero."""
    cfg, _, batch = _setup(small_kg, 4)
    cfg = dataclasses.replace(
        cfg, decoder=decoder, negative_sampler=sampler,
        rgcn=dataclasses.replace(cfg.rgcn, dropout=0.3))
    params = init_kge_params(jax.random.PRNGKey(0), cfg)
    assert batch["core_edge_index"].shape[1] < batch["src"].shape[1]
    for i in range(2):
        part = jax.tree_util.tree_map(lambda x: x[i], batch)
        key = jax.random.PRNGKey(10 + i)
        l_c, g_c = jax.value_and_grad(lambda p: fullgraph_loss(
            p, cfg, part, key, train=train)[0])(params)
        l_m, g_m = jax.value_and_grad(lambda p: _masked_fullgraph_loss(
            p, cfg, part, key, train))(params)
        # the loss sums its rows in a tree whose shape follows the row
        # count, so it may round differently; the gradient may not
        np.testing.assert_allclose(float(l_c), float(l_m), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g_c),
                        jax.tree_util.tree_leaves(g_m)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fullgraph_loss_weights_each_negative_by_its_own_edge(small_kg):
    """With s = 2 negatives per edge, every negative is weighted by the
    core mask of the edge it corrupts: the loss equals a per-triplet loop
    over the local edges."""
    from repro.core.negative import corrupt_triplets
    from repro.models.kge import encode_partition
    cfg, params, batch = _setup(small_kg, 4)
    s = 2
    cfg = dataclasses.replace(cfg, num_negatives=s)
    part = jax.tree_util.tree_map(lambda x: x[1], batch)
    key = jax.random.PRNGKey(7)
    loss = float(fullgraph_loss(params, cfg, part, key, train=False)[0])

    k_neg, _ = jax.random.split(key)
    h = np.asarray(encode_partition(params, cfg, part), np.float64)
    rel_diag = np.asarray(params["decoder"]["rel_diag"], np.float64)
    pos = np.stack([np.asarray(part[k]) for k in ("src", "rel", "dst")], 1)
    neg = np.asarray(corrupt_triplets(k_neg, jnp.asarray(pos), s,
                                      part["num_core_vertices"])[0])
    core = np.asarray(part["core_edge_mask"])

    def bce(t, label):
        x = float(np.sum(h[t[0]] * rel_diag[t[1]] * h[t[2]]))
        return max(x, 0.0) - x * label + np.log1p(np.exp(-abs(x)))

    total = count = 0.0
    for e in range(pos.shape[0]):
        if not core[e]:
            continue
        total += bce(pos[e], 1.0)
        for j in range(s):
            total += bce(neg[e * s + j], 0.0)
        count += 1 + s
    assert count == (1 + s) * int(part["num_core_edges"])
    np.testing.assert_allclose(loss, total / count, rtol=1e-5)


_DATA_MESH_SCRIPT = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 2, jax.devices()
from repro.core import expand_all, make_synthetic_kg, pad_partitions, \\
    partition_graph
from repro.launch.mesh import make_host_mesh
from repro.models import KGEConfig, RGCNConfig, fullgraph_loss, \\
    init_kge_params
from repro.training import adam
from repro.training.distributed import (
    make_simulated_train_step, make_spmd_train_step,
)

kg = make_synthetic_kg(200, 6, 1600, seed=1).with_inverse_relations()
pb = pad_partitions(expand_all(
    kg, partition_graph(kg, 2, "vertex_cut", seed=0), 2))
assert pb.core_edge_index.shape[1] < pb.padded_edges
batch = {f.name: jnp.asarray(getattr(pb, f.name))
         for f in dataclasses.fields(pb)}
mesh = make_host_mesh(2, 1)                      # data=2 x model=1
opt = adam(0.01)
for s in (1, 2):
    cfg = KGEConfig(rgcn=RGCNConfig(
        num_entities=kg.num_entities, num_relations=kg.num_relations,
        hidden_dim=16, num_layers=2, num_bases=2, dropout=0.2),
        num_negatives=s)
    params = init_kge_params(jax.random.PRNGKey(0), cfg)
    loss = lambda p, b, k: fullgraph_loss(p, cfg, b, k)
    step_spmd = make_spmd_train_step(loss, opt, mesh)
    step_sim = make_simulated_train_step(loss, opt)
    states = [(params, opt.init(params)), (params, opt.init(params))]
    for t in range(2):
        keys = jax.random.split(jax.random.PRNGKey(2 + t), 2)
        outs = [step(p, o, batch, keys)
                for step, (p, o) in zip((step_spmd, step_sim), states)]
        (p1, o1, m1), (p2, o2, m2) = outs
        assert float(m1["loss"]) == float(m2["loss"]), (s, t)
        for a, b in zip(jax.tree_util.tree_leaves((p1, o1)),
                        jax.tree_util.tree_leaves((p2, o2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        states = [(p1, o1), (p2, o2)]
print("DATA_MESH_OK")
"""


def test_spmd_fullgraph_core_rows_two_device_data_mesh_matches_simulated():
    """On a forced 2-device data mesh (one trainer per device) the spmd
    full-graph step, scoring each trainer's core rows, is bitwise the
    simulated step: losses, parameters and optimizer state, two steps
    deep, with one and with two negatives per edge."""
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _DATA_MESH_SCRIPT], cwd=repo, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DATA_MESH_OK" in proc.stdout


@pytest.mark.slow
def test_end_to_end_accuracy_parity():
    """Table 3 structure at toy scale: 4-trainer distributed training
    matches 1-trainer metrics within tolerance."""
    splits = synthetic_fb15k(scale=0.015, seed=3)
    results = {}
    for p in (1, 4):
        tr = KGETrainer(splits, TrainConfig(
            num_trainers=p, epochs=12, hidden_dim=24, batch_size=None,
            learning_rate=0.05, seed=0))
        tr.fit()
        results[p] = tr.evaluate("test")
    # distributed must stay within 25% relative of non-distributed MRR
    # (paper: identical to 2 decimals at real scale/epochs)
    assert results[4]["test_mrr"] > 0.5 * results[1]["test_mrr"]
    assert results[4]["test_mrr"] > 0.05


def test_trainer_keys_differ_across_trainers():
    from repro.training import split_trainer_keys
    keys = split_trainer_keys(jax.random.PRNGKey(0), 4, step=3)
    assert keys.shape[0] == 4
    assert len({tuple(np.asarray(k).tolist()) for k in keys}) == 4


# ====================================================================== #
# The REAL shard_map step (make_spmd_train_step)
# ====================================================================== #
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_spmd_step_runs_sgd_opt_state(small_kg, momentum):
    """Regression: the spmd step's optimizer-state specs are derived from
    the REAL state structure (``derive_opt_state_specs``), not a
    hardcoded adam-shaped ``OptState(step, mu, nu)`` — plain SGD
    (``mu=None, nu=None``) and momentum SGD (``nu=None``) trace-errored
    before.  On the degenerate 1x1 mesh the step must also stay bitwise
    equal to the vmap simulation."""
    from repro.launch.mesh import make_host_mesh
    from repro.training.distributed import make_spmd_train_step
    from repro.training.optimizer import sgd

    cfg, params, batch = _setup(small_kg, 1)
    opt = sgd(0.05, momentum=momentum)
    keys = jnp.stack([jax.random.PRNGKey(2)])

    def loss_one(p, b, k):
        return fullgraph_loss(p, cfg, b, k, train=False)

    step_spmd = make_spmd_train_step(loss_one, opt, make_host_mesh(1, 1))
    step_sim = make_simulated_train_step(loss_one, opt)
    p1, o1, m1 = step_spmd(params, opt.init(params), batch, keys)
    p2, o2, m2 = step_sim(params, opt.init(params), batch, keys)
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree_util.tree_leaves((p1, o1)),
                    jax.tree_util.tree_leaves((p2, o2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_derive_opt_state_specs_structures():
    from jax.sharding import PartitionSpec as P
    from repro.training.distributed import derive_opt_state_specs
    from repro.training.optimizer import adam, sgd

    params = {"w": jnp.ones((4, 2)), "b": jnp.ones((2,))}
    p_spec = {"w": P("model"), "b": P()}
    for opt, has_mu, has_nu in [(adam(0.1), True, True),
                                (sgd(0.1), False, False),
                                (sgd(0.1, momentum=0.9), True, False)]:
        state = opt.init(params)
        specs = derive_opt_state_specs(state, params, p_spec)
        assert specs.step == P()
        assert (specs.mu == p_spec) if has_mu else (specs.mu is None)
        assert (specs.nu == p_spec) if has_nu else (specs.nu is None)
        # the spec tree must mirror the state tree exactly
        assert (jax.tree_util.tree_structure(specs, is_leaf=lambda x:
                isinstance(x, P)) == jax.tree_util.tree_structure(state))


def test_trainer_spmd_flag_resolution():
    """cfg.spmd tri-state on the single local CPU device: auto stays on
    the simulated step, False stays off, True forces the 1x1-mesh spmd
    step (and errors when the model axis cannot fit)."""
    splits = synthetic_fb15k(scale=0.01, seed=3)
    base = dict(num_trainers=2, epochs=1, hidden_dim=8, num_hops=1,
                batch_size=64)
    assert not KGETrainer(splits, TrainConfig(**base))._spmd
    assert not KGETrainer(splits, TrainConfig(spmd=False, **base))._spmd
    tr = KGETrainer(splits, TrainConfig(spmd=True, **base))
    assert tr._spmd and dict(tr.mesh.shape) == {"data": 1, "model": 1}
    if jax.device_count() == 1:
        with pytest.raises(ValueError, match="model-axis"):
            KGETrainer(splits, TrainConfig(spmd=True, num_table_shards=2,
                                           **base))


def test_trainer_exchange_validation():
    """A sim-only exchange under spmd (and vice versa) fails at trainer
    construction, not deep inside a trace."""
    splits = synthetic_fb15k(scale=0.01, seed=3)
    base = dict(num_trainers=2, epochs=1, hidden_dim=8, num_hops=1,
                batch_size=64, num_table_shards=1)
    with pytest.raises(ValueError, match="not available"):
        KGETrainer(splits, TrainConfig(spmd=True, gather_exchange="fused",
                                       **base))
    with pytest.raises(ValueError, match="not available"):
        KGETrainer(splits, TrainConfig(spmd=False, gather_exchange="psum",
                                       **base))


def test_trainer_forced_spmd_matches_simulated_one_device():
    """spmd=True on the single CPU device (1x1 mesh): per-epoch losses
    float-identical and final params bitwise vs the simulated step."""
    splits = synthetic_fb15k(scale=0.01, seed=3)
    base = dict(num_trainers=2, epochs=2, hidden_dim=8, num_hops=1,
                batch_size=64, seed=0)
    losses, finals = [], []
    for spmd in (False, True):
        tr = KGETrainer(splits, TrainConfig(spmd=spmd, **base))
        losses.append([tr.train_epoch()["loss"] for _ in range(2)])
        finals.append(jax.device_get(tr.params))
        tr.close()
    assert losses[0] == losses[1]
    for a, b in zip(jax.tree_util.tree_leaves(finals[0]),
                    jax.tree_util.tree_leaves(finals[1])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n,block", [(8, 4), (8, 2), (16, 4), (4, 2)])
def test_pairwise_sum_block_is_a_subtree(n, block):
    """Summing each aligned block of trainers first, then the block sums,
    is bitwise the one-device sum: the order the spmd step (one block
    per data-axis device) shares with the simulated step."""
    from repro.models.decoders import pairwise_sum
    rng = np.random.default_rng(n * block)
    x = jnp.asarray(rng.normal(size=(n, 33))
                    * 10.0 ** rng.integers(-6, 6, size=(n, 1)), jnp.float32)
    blocks = jnp.stack([pairwise_sum(x[i:i + block])
                        for i in range(0, n, block)])
    assert (np.asarray(pairwise_sum(blocks))
            == np.asarray(pairwise_sum(x))).all()


def test_pairwise_sum_odd_count_carries_the_last_element():
    from repro.models.decoders import pairwise_sum
    x = jnp.asarray([1e8, 1.0, -1e8, 3.0, 0.5], jnp.float32)
    want = ((x[0] + x[1]) + (x[2] + x[3])) + x[4]
    assert np.asarray(pairwise_sum(x)) == np.asarray(want)


# The tentpole gate: on a FORCED 2-device mesh the spmd trainer (auto-on)
# must be float-identical in per-epoch losses and bitwise in final params
# to the simulated trainer, for the mini-batch AND full-graph paths with a
# 2-shard entity table.  Subprocess: the host device count must be forced
# before any jax import.
_SPMD_TRAINER_SCRIPT = """
import jax, numpy as np
assert jax.device_count() == 2, jax.devices()
from repro.data import synthetic_fb15k
from repro.training import KGETrainer, TrainConfig

splits = synthetic_fb15k(scale=0.01, seed=3)
base = dict(num_trainers=2, epochs=2, hidden_dim=8, num_hops=1, seed=0,
            num_table_shards=2)
for bs in (64, None):
    runs = []
    for spmd in (False, None):                 # None = auto -> on
        tr = KGETrainer(splits, TrainConfig(
            batch_size=bs, spmd=spmd, **base))
        assert tr._spmd == (spmd is None)
        if tr._spmd:
            assert dict(tr.mesh.shape) == {"data": 1, "model": 2}
        losses = [tr.train_epoch()["loss"] for _ in range(2)]
        runs.append((losses, jax.device_get(tr.params)))
        tr.close()
    (l_sim, p_sim), (l_spmd, p_spmd) = runs
    assert l_sim == l_spmd, (bs, l_sim, l_spmd)
    for a, b in zip(jax.tree_util.tree_leaves(p_sim),
                    jax.tree_util.tree_leaves(p_spmd)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK", "minibatch" if bs else "fullgraph")
print("SPMD_TRAINER_OK")
"""


@pytest.mark.slow
def test_spmd_trainer_two_device_matches_simulated():
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _SPMD_TRAINER_SCRIPT], cwd=repo, env=env,
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SPMD_TRAINER_OK" in proc.stdout
