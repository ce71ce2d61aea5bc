"""Sharded-entity-table equivalence suite (repro.sharding.embedding).

The contract under test: row-sharding the entity embedding table over the
``model`` axis — shard-local gather + exchange, driven by host-precomputed
``ShardedGatherPlan``s or the identical in-jit plan — is BITWISE equal to
the replicated dense gather for forward, loss and gradients, at 1, 2 and 4
shards on the simulated mesh, including out-of-order and duplicate gather
indices.  Exactly one shard owns each row, so every output element is one
real value plus zeros, and the transpose scatter-adds the same cotangents
per row.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import expand_all, pad_partitions, partition_graph, \
    plan_budgets
from repro.data.pipeline import SerialMinibatchPipeline
from repro.models import (
    KGEConfig, RGCNConfig, fullgraph_loss, init_kge_params, minibatch_loss,
)
from repro.sharding.embedding import (
    ShardedGatherPlan, ShardedTableLayout, convert_table_layout,
    dequantize_rows, plan_local_gather, plan_local_gather_device,
    quantize_rows, shard_table, sharded_gather, unshard_table,
)

SHARD_COUNTS = (1, 2, 4)


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ====================================================================== #
# Layout + plans
# ====================================================================== #
class TestLayout:
    @pytest.mark.parametrize("v,s", [(300, 1), (300, 2), (301, 4), (7, 4)])
    def test_shard_unshard_roundtrip(self, v, s):
        lay = ShardedTableLayout(v, s)
        table = np.random.default_rng(0).normal(
            size=(v, 8)).astype(np.float32)
        sh = shard_table(table, lay)
        assert sh.shape == (s, lay.rows_per_shard, 8)
        assert lay.padded_rows >= v
        np.testing.assert_array_equal(unshard_table(sh, v), table)

    def test_bytes_per_device_shrink_inverse_in_shards(self):
        lay1 = ShardedTableLayout(4096, 1)
        for s in (2, 4, 8):
            lays = ShardedTableLayout(4096, s)
            assert lays.bytes_per_shard(64) * s == lay1.bytes_per_shard(64)

    def test_invalid_layout_rejected(self):
        with pytest.raises(ValueError, match="invalid layout"):
            ShardedTableLayout(0, 2)

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_host_plan_matches_device_plan(self, s):
        lay = ShardedTableLayout(301, s)
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 301, size=64).astype(np.int32)
        li, ow = plan_local_gather(lay, ids)
        li_d, ow_d = plan_local_gather_device(
            s, lay.rows_per_shard, jnp.asarray(ids))
        np.testing.assert_array_equal(li, np.asarray(li_d))
        np.testing.assert_array_equal(ow, np.asarray(ow_d))
        # exactly one shard owns every id; local ids stay in range
        np.testing.assert_array_equal(ow.sum(axis=0), np.ones(64))
        assert li.min() >= 0 and li.max() < lay.rows_per_shard

    def test_stacked_plan_layout(self):
        lay = ShardedTableLayout(100, 4)
        g = np.arange(12, dtype=np.int32).reshape(3, 4) * 7  # (P=3, V=4)
        plan = ShardedGatherPlan.for_stacked(lay, g)
        assert plan.local_ids.shape == plan.owned.shape == (3, 4, 4)
        for p in range(3):
            li, ow = plan_local_gather(lay, g[p])
            np.testing.assert_array_equal(plan.local_ids[p], li)
            np.testing.assert_array_equal(plan.owned[p], ow)


# ====================================================================== #
# Gather: forward + gradient bitwise vs dense, dup/out-of-order indices
# ====================================================================== #
class TestShardedGatherBitwise:
    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_forward_and_grad_match_dense(self, s):
        v, d = 301, 16
        table = jax.random.normal(jax.random.PRNGKey(0), (v, d))
        # out-of-order, duplicated, boundary-hitting gather indices
        ids = np.array([5, 3, 5, 0, v - 1, 3, 299, 150, 150, 7, 0, v - 1],
                       np.int32)
        lay = ShardedTableLayout(v, s)
        shards = shard_table(table, lay)
        li, ow = plan_local_gather(lay, ids)
        li, ow = jnp.asarray(li), jnp.asarray(ow)

        dense = np.asarray(table[ids])
        got = np.asarray(sharded_gather(shards, li, ow))
        np.testing.assert_array_equal(got, dense)

        w = jnp.arange(1.0, d + 1)

        def loss_dense(t):
            return jnp.sum(jnp.tanh(t[ids]) * w)

        def loss_sharded(t):
            return jnp.sum(jnp.tanh(sharded_gather(t, li, ow)) * w)

        g_dense = np.asarray(jax.grad(loss_dense)(table))
        g_sh = jax.grad(loss_sharded)(shards)
        np.testing.assert_array_equal(
            np.asarray(unshard_table(g_sh, v)), g_dense)
        # padding rows are never gathered -> exactly zero gradient
        pad = np.asarray(g_sh).reshape(-1, d)[v:]
        assert (pad == 0).all()

    def test_shard_map_branch_rejects_replicated_table(self):
        """Passing a full (S>1, rows, d) stack with an axis_name (i.e. a
        replicated table inside shard_map — param_specs forgotten) must
        fail at trace time, not psum S wrong-row gathers."""
        lay = ShardedTableLayout(40, 2)
        shards = shard_table(jnp.ones((40, 4)), lay)
        li, ow = plan_local_gather(lay, np.arange(8))
        with pytest.raises(ValueError, match="row block"):
            sharded_gather(shards, jnp.asarray(li), jnp.asarray(ow),
                           axis_name="model")

    @pytest.mark.parametrize("s", SHARD_COUNTS + (8,))
    def test_fused_matches_chain_exchange(self, s):
        """The fused flat-index default is bitwise the original
        take -> mask -> sum chain, forward and grad."""
        v, d = 301, 16
        table = jax.random.normal(jax.random.PRNGKey(1), (v, d))
        ids = np.array([5, 3, 5, 0, v - 1, 3, 299, 150, 150, 7, 0, v - 1],
                       np.int32)
        lay = ShardedTableLayout(v, s)
        shards = shard_table(table, lay)
        li, ow = plan_local_gather(lay, ids)
        li, ow = jnp.asarray(li), jnp.asarray(ow)
        np.testing.assert_array_equal(
            np.asarray(sharded_gather(shards, li, ow, exchange="fused")),
            np.asarray(sharded_gather(shards, li, ow,
                                      exchange="masked_sum")))
        w = jnp.arange(1.0, d + 1)
        g_f = jax.grad(lambda t: jnp.sum(jnp.tanh(sharded_gather(
            t, li, ow, exchange="fused")) * w))(shards)
        g_c = jax.grad(lambda t: jnp.sum(jnp.tanh(sharded_gather(
            t, li, ow, exchange="masked_sum")) * w))(shards)
        np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_c))

    def test_unknown_exchange_rejected(self):
        lay = ShardedTableLayout(40, 2)
        shards = shard_table(jnp.ones((40, 4)), lay)
        li, ow = plan_local_gather(lay, np.arange(8))
        li, ow = jnp.asarray(li), jnp.asarray(ow)
        with pytest.raises(ValueError, match="unknown sim exchange"):
            sharded_gather(shards, li, ow, exchange="psum")
        with pytest.raises(ValueError, match="unknown shard_map exchange"):
            jax.vmap(lambda t: sharded_gather(
                t[None], li, ow, axis_name="model", exchange="fused"),
                axis_name="model")(shards)


# ====================================================================== #
# Exchange layouts under a named axis: psum / psum_scatter / alltoall
# ====================================================================== #
class TestExchangeLayouts:
    """``jax.vmap(axis_name=...)`` drives the shard_map code path (same
    collectives, rank-1 mesh semantics) cheaply on one device: every
    exchange layout must be bitwise equal to the dense gather, including
    a V that is NOT a multiple of S (the pad-around-collective path)."""

    @pytest.mark.parametrize("s", SHARD_COUNTS + (8,))
    @pytest.mark.parametrize("exchange",
                             ("psum", "psum_scatter", "alltoall"))
    def test_exchange_bitwise_vs_dense(self, s, exchange):
        v, d, nids = 301, 16, 41        # 41 % s != 0 for s in (2, 4, 8)
        rng = np.random.default_rng(s)
        table = jax.random.normal(jax.random.PRNGKey(2), (v, d))
        ids = np.concatenate([rng.integers(0, v, nids - 4),
                              [0, v - 1, 5, 5]]).astype(np.int32)
        lay = ShardedTableLayout(v, s)
        shards = shard_table(table, lay)
        li, ow = plan_local_gather(lay, ids)
        li, ow = jnp.asarray(li), jnp.asarray(ow)
        out = jax.vmap(lambda t: sharded_gather(
            t[None], li, ow, axis_name="model", exchange=exchange),
            axis_name="model")(shards)
        dense = np.asarray(table[ids])
        for shard in range(s):          # exchange output is replicated
            np.testing.assert_array_equal(np.asarray(out[shard]), dense)

    @pytest.mark.parametrize("exchange",
                             ("psum", "psum_scatter", "alltoall"))
    def test_exchange_grads_bitwise_vs_dense(self, exchange):
        v, d, s = 201, 8, 4
        table = jax.random.normal(jax.random.PRNGKey(3), (v, d))
        ids = np.array([7, 7, 0, v - 1, 50, 50, 50, 3, 9], np.int32)
        lay = ShardedTableLayout(v, s)
        shards = shard_table(table, lay)
        li, ow = plan_local_gather(lay, ids)
        li, ow = jnp.asarray(li), jnp.asarray(ow)
        w = jnp.arange(1.0, d + 1)

        def loss(stack):
            out = jax.vmap(lambda t: sharded_gather(
                t[None], li, ow, axis_name="model", exchange=exchange),
                axis_name="model")(stack)
            # vmap inlines the exchange's custom VJP when it batches it, so
            # this path exercises the COLLECTIVE-TRANSPOSE backward: the
            # loss must consume the replicated output exactly once (shard
            # 0's copy) for the broadcast cotangent to match dense.  The
            # real shard_map path instead computes the loss replicated on
            # every device and uses the identity backward — gated bitwise
            # by the 2-device subprocess tests (test_sharded_embedding /
            # test_distributed slow tier).
            return jnp.sum(jnp.tanh(out[0]) * w)

        g_sh = jax.grad(loss)(shards)
        g_d = jax.grad(lambda t: jnp.sum(jnp.tanh(t[ids]) * w))(table)
        np.testing.assert_array_equal(
            np.asarray(unshard_table(g_sh, v)), np.asarray(g_d))


# ====================================================================== #
# Plan dedup: unique-id gather + on-device inverse expansion
# ====================================================================== #
class TestDedupPlans:
    def _check(self, lay, table, dense, ids, pad_multiple=8):
        from repro.sharding.embedding import plan_unique_gather
        li, ow, inv = plan_unique_gather(lay, ids,
                                         pad_multiple=pad_multiple)
        u = len(np.unique(ids))
        assert li.shape[1] % pad_multiple == 0 and li.shape[1] >= u
        # padding slots are owned by NO shard -> exact zero rows
        np.testing.assert_array_equal(ow.sum(axis=0)[:u], np.ones(u))
        np.testing.assert_array_equal(ow.sum(axis=0)[u:],
                                      np.zeros(li.shape[1] - u))
        out = sharded_gather(table, jnp.asarray(li), jnp.asarray(ow),
                             inverse=jnp.asarray(inv))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(dense[ids]))
        return li, ow, inv

    @pytest.mark.parametrize("s", SHARD_COUNTS + (8,))
    def test_dedup_bitwise_vs_dense(self, s):
        v, d = 301, 16
        dense = jax.random.normal(jax.random.PRNGKey(4), (v, d))
        lay = ShardedTableLayout(v, s)
        table = shard_table(dense, lay)
        ids = np.array([5, 3, 5, 0, v - 1, 3, 299, 150, 150, 7, 0, v - 1],
                       np.int32)
        li, _, _ = self._check(lay, table, dense, ids)
        assert li.shape[1] < len(ids) + 8   # it actually deduped

    def test_all_duplicate_batch(self):
        """Every slot the same id: one exchanged row, V-way expansion, and
        the gradient accumulates V cotangents into ONE row — bitwise equal
        to the dense gather's scatter-add."""
        v, d, s = 120, 8, 4
        dense = jax.random.normal(jax.random.PRNGKey(5), (v, d))
        lay = ShardedTableLayout(v, s)
        table = shard_table(dense, lay)
        ids = np.full(17, 42, np.int32)
        li, ow, inv = self._check(lay, table, dense, ids)
        assert ow.sum() == 1                      # one owned slot total
        w = jnp.arange(1.0, d + 1)
        g_sh = jax.grad(lambda t: jnp.sum(jnp.tanh(sharded_gather(
            t, jnp.asarray(li), jnp.asarray(ow),
            inverse=jnp.asarray(inv))) * w))(table)
        g_d = jax.grad(
            lambda t: jnp.sum(jnp.tanh(t[ids]) * w))(dense)
        np.testing.assert_array_equal(
            np.asarray(unshard_table(g_sh, v)), np.asarray(g_d))

    def test_single_shard_batch(self):
        """A batch whose ids all live on one shard: the other shards own
        nothing and contribute exact zeros."""
        v, d, s = 200, 8, 4
        dense = jax.random.normal(jax.random.PRNGKey(6), (v, d))
        lay = ShardedTableLayout(v, s)
        table = shard_table(dense, lay)
        rows = lay.rows_per_shard
        ids = np.arange(2 * rows, 2 * rows + 10, dtype=np.int32)  # shard 2
        li, ow, _ = self._check(lay, table, dense, ids)
        assert (ow[[0, 1, 3]] == 0).all() and ow[2].sum() == 10

    def test_empty_shards_on_ragged_block(self):
        """Ragged last shard (301 rows / 4 shards -> 3 pad rows): ids
        clustered at the front leave the tail shard completely unowned,
        and the layout's zero-padded tail rows are never touched."""
        v, d, s = 301, 8, 4
        dense = jax.random.normal(jax.random.PRNGKey(7), (v, d))
        lay = ShardedTableLayout(v, s)
        assert lay.padded_rows > v   # genuinely ragged
        table = shard_table(dense, lay)
        ids = np.array([0, 1, 2, 1, 0, 2, 2], np.int32)
        li, ow, inv = self._check(lay, table, dense, ids)
        assert ow[-1].sum() == 0     # tail shard owns nothing
        g_sh = jax.grad(lambda t: jnp.sum(sharded_gather(
            t, jnp.asarray(li), jnp.asarray(ow),
            inverse=jnp.asarray(inv)) ** 2))(table)
        pad = np.asarray(g_sh).reshape(-1, d)[v:]
        assert (pad == 0).all()      # padding rows get exactly zero grad

    def test_grad_accumulation_head_and_tail_dup(self):
        """One id in both the first and last slot: the inverse expansion's
        transpose must accumulate both slots' cotangents into the single
        exchanged row — bitwise vs dense (same scatter-add order)."""
        v, d, s = 150, 8, 2
        dense = jax.random.normal(jax.random.PRNGKey(8), (v, d))
        lay = ShardedTableLayout(v, s)
        table = shard_table(dense, lay)
        ids = np.array([99] + list(range(10, 20)) + [99], np.int32)
        from repro.sharding.embedding import plan_unique_gather
        li, ow, inv = plan_unique_gather(lay, ids, pad_multiple=8)
        li, ow, inv = jnp.asarray(li), jnp.asarray(ow), jnp.asarray(inv)
        # distinct per-slot weights so head/tail cotangents differ
        w = jnp.arange(1.0, len(ids) + 1)[:, None] * jnp.arange(1.0, d + 1)
        g_sh = jax.grad(lambda t: jnp.sum(jnp.tanh(sharded_gather(
            t, li, ow, inverse=inv)) * w))(table)
        g_d = jax.grad(lambda t: jnp.sum(jnp.tanh(t[ids]) * w))(dense)
        np.testing.assert_array_equal(
            np.asarray(unshard_table(g_sh, v)), np.asarray(g_d))

    def test_stacked_dedup_plan(self):
        """for_stacked(dedup=True): per-row uniques share one bucket, and
        every row's inverse expansion reproduces its dense gather."""
        lay = ShardedTableLayout(100, 4)
        g = np.array([[7, 7, 7, 7, 7, 7],          # 1 unique
                      [0, 99, 0, 99, 50, 50],      # 3 uniques
                      [1, 2, 3, 4, 5, 6]], np.int32)   # 6 uniques
        plan = ShardedGatherPlan.for_stacked(lay, g, dedup=True,
                                             pad_multiple=4)
        assert plan.local_ids.shape == (3, 4, 8)   # bucket = ceil(6/4)*4
        assert plan.inverse.shape == g.shape
        dense = jax.random.normal(jax.random.PRNGKey(9), (100, 8))
        table = shard_table(dense, lay)
        for p in range(3):
            out = sharded_gather(
                table, jnp.asarray(plan.local_ids[p]),
                jnp.asarray(plan.owned[p]),
                inverse=jnp.asarray(plan.inverse[p]))
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(dense[g[p]]))

    def test_plan_unique_rejects_stacked_input(self):
        from repro.sharding.embedding import plan_unique_gather
        with pytest.raises(ValueError, match="expects"):
            plan_unique_gather(ShardedTableLayout(10, 2),
                               np.zeros((2, 3), np.int32))


# ====================================================================== #
# Model-level equivalence: vertex_input / losses / gradients
# ====================================================================== #
def _configs(kg, s):
    rgcn = dict(num_entities=kg.num_entities, num_relations=kg.num_relations,
                hidden_dim=16, num_layers=2, num_bases=2, dropout=0.0)
    dense = KGEConfig(rgcn=RGCNConfig(**rgcn))
    sharded = KGEConfig(rgcn=RGCNConfig(**rgcn, num_table_shards=s))
    return dense, sharded


def _sharded_params(params, kg, s):
    out = dict(params)
    out["entity_embedding"] = shard_table(
        params["entity_embedding"], ShardedTableLayout(kg.num_entities, s))
    return out


class TestModelEquivalence:
    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_minibatch_loss_and_grads_bitwise(self, small_kg, s):
        parts = expand_all(
            small_kg, partition_graph(small_kg, 2, "vertex_cut", seed=0), 2)
        budget = plan_budgets(parts, 32, 1, 2, seed=0)
        pipe = SerialMinibatchPipeline(
            parts, batch_size=32, num_negatives=1, num_hops=2,
            budget=budget, seed=5,
            table_layout=ShardedTableLayout(small_kg.num_entities, s))
        batch = next(pipe.device_batches(1))
        b0 = jax.tree_util.tree_map(lambda x: x[0], batch)
        assert b0["shard_local_ids"].shape[0] == s

        cfg_d, cfg_s = _configs(small_kg, s)
        p_dense = init_kge_params(jax.random.PRNGKey(0), cfg_d)
        p_shard = _sharded_params(p_dense, small_kg, s)
        if s > 1:   # same key => init produces the sharded layout directly
            _tree_equal(p_shard, init_kge_params(jax.random.PRNGKey(0),
                                                 cfg_s))

        def ld(p):
            return minibatch_loss(p, cfg_d, b0)[0]

        def ls(p):
            return minibatch_loss(p, cfg_s, b0)[0]

        (l_d, g_d) = jax.value_and_grad(ld)(p_dense)
        (l_s, g_s) = jax.value_and_grad(ls)(p_shard)
        assert float(l_d) == float(l_s)
        g_s = dict(g_s)
        g_s["entity_embedding"] = unshard_table(
            g_s["entity_embedding"], small_kg.num_entities)
        _tree_equal(g_d, g_s)

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_fullgraph_loss_bitwise_with_on_the_fly_plan(self, small_kg, s):
        """Paths that build gather ids on device (full-graph training,
        evaluation) use the in-jit plan — same result, no host plan."""
        parts = expand_all(
            small_kg, partition_graph(small_kg, 2, "vertex_cut", seed=0), 2)
        pb = pad_partitions(parts)
        part0 = {f.name: jnp.asarray(getattr(pb, f.name)[0])
                 for f in dataclasses.fields(pb)}
        cfg_d, cfg_s = _configs(small_kg, s)
        p_dense = init_kge_params(jax.random.PRNGKey(0), cfg_d)
        p_shard = _sharded_params(p_dense, small_kg, s)
        key = jax.random.PRNGKey(3)
        l_d, _ = fullgraph_loss(p_dense, cfg_d, part0, key, train=False)
        l_s, _ = fullgraph_loss(p_shard, cfg_s, part0, key, train=False)
        assert float(l_d) == float(l_s)

        g_d = jax.grad(lambda p: fullgraph_loss(
            p, cfg_d, part0, key, train=False)[0])(p_dense)
        g_s = dict(jax.grad(lambda p: fullgraph_loss(
            p, cfg_s, part0, key, train=False)[0])(p_shard))
        g_s["entity_embedding"] = unshard_table(
            g_s["entity_embedding"], small_kg.num_entities)
        _tree_equal(g_d, g_s)

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_fullgraph_core_rows_equal_all_rows_sharded(self, small_kg, s):
        """With a row-sharded table, scoring the core rows equals scoring
        every local edge under its core mask (the index set to every
        position), and the padded index slots weigh nothing whatever edge
        they name."""
        parts = expand_all(
            small_kg, partition_graph(small_kg, 4, "vertex_cut", seed=0), 2)
        pb = pad_partitions(parts)
        part = {f.name: jnp.asarray(getattr(pb, f.name)[2])
                for f in dataclasses.fields(pb)}
        e = pb.padded_edges
        n = int(part["num_core_edges"])
        every = {**part, "core_edge_index": jnp.arange(e, dtype=jnp.int32),
                 "num_core_edges": jnp.int32(e)}
        slots = part["core_edge_index"].shape[0]
        shuffled = {**part, "core_edge_index": part["core_edge_index"].at[
            n:].set(jnp.arange(slots - n, dtype=jnp.int32) % e)}
        _, cfg_s = _configs(small_kg, s)
        cfg_s = dataclasses.replace(
            cfg_s, rgcn=dataclasses.replace(cfg_s.rgcn, dropout=0.2))
        p_shard = init_kge_params(jax.random.PRNGKey(0), cfg_s)
        key = jax.random.PRNGKey(4)

        def loss_grad(b):
            return jax.value_and_grad(lambda p: fullgraph_loss(
                p, cfg_s, b, key)[0])(p_shard)

        l_c, g_c = loss_grad(part)
        l_a, g_a = loss_grad(every)
        np.testing.assert_allclose(float(l_c), float(l_a), rtol=1e-6)
        _tree_equal(g_c, g_a)
        l_x, g_x = loss_grad(shuffled)
        assert float(l_x) == float(l_c)
        _tree_equal(g_x, g_c)

    def test_encode_all_entities_matches(self, small_kg):
        from repro.training.evaluation import encode_all_entities
        cfg_d, cfg_s = _configs(small_kg, 2)
        p_dense = init_kge_params(jax.random.PRNGKey(0), cfg_d)
        p_shard = _sharded_params(p_dense, small_kg, 2)
        e_d = encode_all_entities(p_dense, cfg_d, small_kg, 2)
        e_s = encode_all_entities(p_shard, cfg_s, small_kg, 2)
        np.testing.assert_array_equal(e_d, e_s)


# ====================================================================== #
# Trainer-level: full training runs are bitwise identical
# ====================================================================== #
class TestTrainerEquivalence:
    def test_two_shard_minibatch_training_matches(self):
        from repro.data import synthetic_fb15k
        from repro.training import KGETrainer, TrainConfig
        splits = synthetic_fb15k(scale=0.01, seed=3)
        losses = {}
        for s in (1, 2):
            tr = KGETrainer(splits, TrainConfig(
                num_trainers=2, epochs=2, hidden_dim=16, batch_size=64,
                num_negatives=1, learning_rate=0.01, seed=0,
                num_table_shards=s))
            losses[s] = [h["loss"] for h in tr.fit()]
            tr.close()
        assert losses[1] == losses[2]

    @pytest.mark.slow
    def test_multi_shard_sweep_minibatch_and_fullgraph(self):
        """The full equivalence sweep (1, 2, 4 shards × both training
        modes × eval) — the tentpole acceptance run."""
        from repro.data import synthetic_fb15k
        from repro.training import KGETrainer, TrainConfig
        splits = synthetic_fb15k(scale=0.015, seed=3)
        for batch_size in (64, None):          # mini-batch and full-graph
            losses, mrrs = {}, {}
            for s in SHARD_COUNTS:
                tr = KGETrainer(splits, TrainConfig(
                    num_trainers=2, epochs=3, hidden_dim=16,
                    batch_size=batch_size, num_negatives=1,
                    learning_rate=0.01, seed=0, num_table_shards=s))
                losses[s] = [h["loss"] for h in tr.fit()]
                mrrs[s] = tr.evaluate("valid")["valid_mrr"]
                tr.close()
            assert losses[1] == losses[2] == losses[4], (batch_size, losses)
            assert mrrs[1] == mrrs[2] == mrrs[4], (batch_size, mrrs)

    def test_dedup_training_matches(self):
        """gather_dedup rearranges the exchange payload, never the math:
        the full loss trajectory is identical to the non-deduped run."""
        from repro.data import synthetic_fb15k
        from repro.training import KGETrainer, TrainConfig
        splits = synthetic_fb15k(scale=0.01, seed=3)
        losses = {}
        for dedup in (False, True):
            tr = KGETrainer(splits, TrainConfig(
                num_trainers=2, epochs=2, hidden_dim=16, batch_size=64,
                num_negatives=1, learning_rate=0.01, seed=0,
                num_table_shards=2, gather_dedup=dedup))
            if dedup:   # the deduped batch really carries the inverse map
                batch = next(tr.pipeline.device_batches(1))
                assert "shard_inverse" in batch
                assert batch["shard_local_ids"].shape[-1] <= \
                    batch["shard_inverse"].shape[-1] + 64
            losses[dedup] = [h["loss"] for h in tr.fit()]
            tr.close()
        assert losses[False] == losses[True]

    def test_masked_sum_exchange_training_matches_fused(self):
        """The legacy chain exchange and the fused default train
        identically (the fused path's bitwise contract, trainer-level)."""
        from repro.data import synthetic_fb15k
        from repro.training import KGETrainer, TrainConfig
        splits = synthetic_fb15k(scale=0.01, seed=3)
        losses = {}
        for exchange in (None, "masked_sum"):
            tr = KGETrainer(splits, TrainConfig(
                num_trainers=2, epochs=2, hidden_dim=16, batch_size=64,
                num_negatives=1, learning_rate=0.01, seed=0,
                num_table_shards=2, gather_exchange=exchange))
            losses[exchange] = [h["loss"] for h in tr.fit()]
            tr.close()
        assert losses[None] == losses["masked_sum"]

    def test_feature_mode_rejects_sharding(self):
        from repro.data import synthetic_citation2
        from repro.training import KGETrainer, TrainConfig
        splits = synthetic_citation2(scale=0.0003, seed=0)
        with pytest.raises(ValueError, match="learned entity embeddings"):
            KGETrainer(splits, TrainConfig(
                num_trainers=2, epochs=1, batch_size=64,
                num_table_shards=2))


# ====================================================================== #
# shard_map step: sharded params survive the real-mesh code path
# ====================================================================== #
class TestSpmdStep:
    def test_spmd_step_with_sharded_table_matches_simulation(self, small_kg):
        """1×1 host mesh smoke: the shard_map step with a sharded-layout
        table + kge_param_specs + psum exchange runs and matches the vmap
        simulation (multi-device meshes change only the axis size)."""
        from repro.launch.mesh import make_host_mesh
        from repro.sharding import kge_param_specs
        from repro.training import adam
        from repro.training.distributed import (
            make_simulated_train_step, make_spmd_train_step,
        )
        mesh = make_host_mesh(1, 1)
        parts = expand_all(
            small_kg, partition_graph(small_kg, 1, "vertex_cut", seed=0), 2)
        pb = pad_partitions(parts)
        batch = {f.name: jnp.asarray(getattr(pb, f.name))
                 for f in dataclasses.fields(pb)}
        _, cfg = _configs(small_kg, 1)
        params = init_kge_params(jax.random.PRNGKey(0), cfg)
        assert params["entity_embedding"].ndim == 2  # s=1 stays dense
        cfg_s = KGEConfig(rgcn=dataclasses.replace(
            cfg.rgcn, num_table_shards=1))
        p_shard = _sharded_params(params, small_kg, 1)
        specs = kge_param_specs(p_shard, mesh)
        opt = adam(0.01)
        keys = jax.random.split(jax.random.PRNGKey(2), 1)

        def loss_spmd(p, b, k):
            return fullgraph_loss(p, cfg_s, b, k, train=False,
                                  model_axis="model")

        def loss_sim(p, b, k):
            return fullgraph_loss(p, cfg_s, b, k, train=False)

        step_spmd = make_spmd_train_step(loss_spmd, opt, mesh,
                                         param_specs=specs)
        step_sim = make_simulated_train_step(loss_sim, opt)
        p1, _, m1 = step_spmd(p_shard, opt.init(p_shard), batch, keys)
        p2, _, m2 = step_sim(p_shard, opt.init(p_shard), batch, keys)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                                  rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


# ====================================================================== #
# Checkpoint layout conversion primitive
# ====================================================================== #
class TestLayoutConversion:
    def test_dense_sharded_roundtrips(self):
        rng = np.random.default_rng(2)
        dense = rng.normal(size=(101, 8)).astype(np.float32)
        for s in (2, 4):
            lay = ShardedTableLayout(101, s)
            sh = convert_table_layout(dense, (s, lay.rows_per_shard, 8))
            np.testing.assert_array_equal(sh, np.asarray(
                shard_table(dense, lay)))
            back = convert_table_layout(sh, (101, 8))
            np.testing.assert_array_equal(back, dense)
        # resharding 2 -> 4 via contiguous row blocks
        sh2 = convert_table_layout(dense, (2, 51, 8))
        sh4 = convert_table_layout(sh2, (4, 26, 8))
        np.testing.assert_array_equal(
            convert_table_layout(sh4, (101, 8)), dense)

    def test_incompatible_shapes_rejected(self):
        with pytest.raises(ValueError, match="cannot convert"):
            convert_table_layout(np.zeros((10, 8)), (10, 4))

    def test_vocab_mismatch_rejected(self):
        """Layout conversion must not silently truncate or zero-pad a
        checkpoint whose logical row count differs (wrong dataset/config)."""
        with pytest.raises(ValueError, match="disjoint logical row"):
            convert_table_layout(np.zeros((100, 8)), (50, 8))
        with pytest.raises(ValueError, match="disjoint logical row"):
            convert_table_layout(np.zeros((100, 8)), (200, 8))
        with pytest.raises(ValueError, match="disjoint logical row"):
            # (4, 26) can only hold 101..104 logical rows, not 100
            convert_table_layout(np.zeros((100, 8)), (4, 26, 8))
        with pytest.raises(ValueError, match="disjoint logical row"):
            convert_table_layout(np.zeros((2, 51, 8)), (90, 8))

    def test_num_rows_closes_the_padding_ambiguity(self):
        """A sharded shape hides the exact row count in its tail padding;
        the caller's true entity count makes the check exact."""
        # (2, 51) fits any V in 101..102 — undetectable from shapes alone,
        # but num_rows=101 proves the 102-row checkpoint is a wrong vocab
        with pytest.raises(ValueError, match="cannot hold exactly 101"):
            convert_table_layout(np.zeros((102, 8)), (2, 51, 8),
                                 num_rows=101)
        out = convert_table_layout(np.zeros((101, 8)), (2, 51, 8),
                                   num_rows=101)
        assert out.shape == (2, 51, 8)
        # and through the checkpoint seam
        import jax
        from repro.models import KGEConfig, RGCNConfig, init_kge_params
        from repro.training import restore_checkpoint, save_checkpoint
        import tempfile
        p_shard = init_kge_params(jax.random.PRNGKey(0), KGEConfig(
            rgcn=RGCNConfig(num_entities=101, num_relations=6,
                            hidden_dim=16, num_layers=2, num_bases=2,
                            num_table_shards=2)))
        p_dense_102 = init_kge_params(jax.random.PRNGKey(0), KGEConfig(
            rgcn=RGCNConfig(num_entities=102, num_relations=6,
                            hidden_dim=16, num_layers=2, num_bases=2)))
        with tempfile.TemporaryDirectory() as tmp:
            path = save_checkpoint(tmp, 1, p_dense_102)
            with pytest.raises(ValueError, match="cannot hold exactly"):
                restore_checkpoint(path, p_shard, entity_rows=101)


# ====================================================================== #
# Quantized (int8) table: the straight-through gather contract at every
# shard count and exchange layout
# ====================================================================== #
class TestQuantizedGatherSweep:
    """``table_dtype="int8"`` sweep: forward within the per-row ``scale/2``
    bound of dense fp32 at 1/2/4 shards, master-weight gradients BITWISE
    equal to the fp32 path on the identical dequantized inputs (the
    straight-through backward is the same scatter-add), and every
    shard_map exchange layout bitwise equal to the single-device int8
    simulation."""

    V, D = 301, 16

    def _setup(self, s):
        table = jax.random.normal(jax.random.PRNGKey(4), (self.V, self.D))
        # duplicates, out-of-order, boundary rows; 13 ids so V_b % s != 0
        # for s in (2, 4) — the pad-around-collective path
        ids = np.array([5, 3, 5, 0, self.V - 1, 3, 299, 150, 150, 7, 0,
                        self.V - 1, 42], np.int32)
        lay = ShardedTableLayout(self.V, s)
        shards = shard_table(table, lay)
        li, ow = plan_local_gather(lay, ids)
        return table, ids, shards, jnp.asarray(li), jnp.asarray(ow)

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_forward_within_half_scale_of_dense(self, s):
        table, ids, shards, li, ow = self._setup(s)
        codes, scales = quantize_rows(np.asarray(shards))
        out = np.asarray(sharded_gather(shards, li, ow, table_dtype="int8"))
        dense = np.asarray(table)[ids]
        # contiguous row blocks put global row g at flat row g
        row_scale = scales.reshape(-1)[ids]
        assert (np.abs(out - dense) <= row_scale[:, None] / 2.0).all()
        # and bitwise equal to the dense gather of the dequantized master
        dq = np.asarray(dequantize_rows(codes, scales))
        np.testing.assert_array_equal(out, dq.reshape(-1, self.D)[ids])

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_loss_and_grads_within_tolerance_of_fp32(self, s):
        table, ids, shards, li, ow = self._setup(s)
        w = jnp.arange(1.0, self.D + 1)

        def loss(t, dtype):
            return jnp.sum(jnp.tanh(
                sharded_gather(t, li, ow, table_dtype=dtype)) * w)

        l8, g8 = jax.value_and_grad(loss)(shards, "int8")
        lf, gf = jax.value_and_grad(loss)(shards, "fp32")
        # |tanh(a) - tanh(b)| <= |a - b| <= scale/2 per gathered element,
        # so the loss bound is sum(w) * scale_max / 2 per batch slot and
        # the per-table-element grad bound follows from |tanh'| shifts
        # (<= 2|a-b|) times the duplicate count (<= 3 here)
        _, scales = quantize_rows(np.asarray(shards))
        s_max = float(scales.max())
        assert abs(float(l8) - float(lf)) <= \
            len(ids) * float(jnp.sum(w)) * s_max / 2.0
        np.testing.assert_allclose(np.asarray(g8), np.asarray(gf),
                                   atol=3 * self.D * s_max, rtol=0)

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_master_grads_bitwise_fp32_path_on_dequant(self, s):
        _, ids, shards, li, ow = self._setup(s)
        dq = jnp.asarray(dequantize_rows(*quantize_rows(np.asarray(shards))))
        w = jnp.arange(1.0, self.D + 1)

        def loss(t, dtype):
            return jnp.sum(jnp.tanh(
                sharded_gather(t, li, ow, table_dtype=dtype)) * w)

        lq, gq = jax.value_and_grad(loss)(shards, "int8")
        lf, gf = jax.value_and_grad(loss)(dq, "fp32")
        assert float(lq) == float(lf)
        np.testing.assert_array_equal(np.asarray(gq), np.asarray(gf))

    @pytest.mark.parametrize("exchange",
                             ("psum", "psum_scatter", "alltoall"))
    @pytest.mark.parametrize("s", (2, 4))
    def test_spmd_exchange_matches_sim_and_fp32_grads(self, s, exchange):
        _, ids, shards, li, ow = self._setup(s)
        sim = np.asarray(sharded_gather(shards, li, ow, table_dtype="int8"))
        w = jnp.arange(1.0, self.D + 1)

        def spmd_loss_and_out(stack, dtype):
            out = jax.vmap(lambda t: sharded_gather(
                t[None], li, ow, axis_name="model", exchange=exchange,
                table_dtype=dtype), axis_name="model")(stack)
            return out

        out = spmd_loss_and_out(shards, "int8")
        for shard in range(s):          # replicated output == simulation
            np.testing.assert_array_equal(np.asarray(out[shard]), sim)

        # int8 spmd master grads == fp32 spmd grads at the dequantized
        # master (same vmap-inlined collective-transpose backward path as
        # the fp32 exchange grad test above: loss consumes shard 0's copy)
        dq = jnp.asarray(dequantize_rows(*quantize_rows(np.asarray(shards))))

        def loss(stack, dtype):
            return jnp.sum(jnp.tanh(spmd_loss_and_out(stack, dtype)[0]) * w)

        gq = jax.grad(loss)(shards, "int8")
        gf = jax.grad(loss)(dq, "fp32")
        np.testing.assert_array_equal(np.asarray(gq), np.asarray(gf))

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_fullgraph_loss_matches_fp32_on_dequantized_master(
            self, small_kg, s):
        """Model-level: the int8 full-graph loss and ALL parameter
        gradients are bitwise what the fp32 model produces when handed the
        dequantized master table — the quantizer is exactly a forward-only
        table substitution."""
        parts = expand_all(
            small_kg, partition_graph(small_kg, 2, "vertex_cut", seed=0), 2)
        pb = pad_partitions(parts)
        part0 = {f.name: jnp.asarray(getattr(pb, f.name)[0])
                 for f in dataclasses.fields(pb)}
        rgcn = dict(num_entities=small_kg.num_entities,
                    num_relations=small_kg.num_relations, hidden_dim=16,
                    num_layers=2, num_bases=2, dropout=0.0,
                    num_table_shards=s)
        cfg8 = KGEConfig(rgcn=RGCNConfig(**rgcn, table_dtype="int8"))
        cfgf = KGEConfig(rgcn=RGCNConfig(**rgcn))
        p = init_kge_params(jax.random.PRNGKey(0), cfgf)
        emb = np.asarray(p["entity_embedding"])
        dq = dequantize_rows(*quantize_rows(
            emb if emb.ndim == 3 else emb[None]))
        p_dq = dict(p)
        p_dq["entity_embedding"] = jnp.asarray(
            dq if emb.ndim == 3 else dq[0])
        key = jax.random.PRNGKey(3)
        l8, g8 = jax.value_and_grad(lambda q: fullgraph_loss(
            q, cfg8, part0, key, train=False)[0])(p)
        lf, gf = jax.value_and_grad(lambda q: fullgraph_loss(
            q, cfgf, part0, key, train=False)[0])(p_dq)
        assert float(l8) == float(lf)
        _tree_equal(g8, gf)
        # and the quantization error stays small at model level
        l_fp32 = fullgraph_loss(p, cfgf, part0, key, train=False)[0]
        np.testing.assert_allclose(float(l8), float(l_fp32), rtol=0.05)


# ====================================================================== #
# Real multi-device mesh: the psum exchange itself (subprocess: forcing
# host device count must happen before jax import)
# ====================================================================== #
_TWO_DEVICE_SCRIPT = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 2, jax.devices()
from repro.core import expand_all, make_synthetic_kg, pad_partitions, \\
    partition_graph
from repro.launch.mesh import make_host_mesh
from repro.models import KGEConfig, RGCNConfig, fullgraph_loss, \\
    init_kge_params
from repro.sharding import kge_param_specs
from repro.training import adam
from repro.training.distributed import (
    make_simulated_train_step, make_spmd_train_step,
)

kg = make_synthetic_kg(150, 6, 1200, seed=1).with_inverse_relations()
parts = expand_all(kg, partition_graph(kg, 1, "vertex_cut", seed=0), 2)
pb = pad_partitions(parts)
batch = {f.name: jnp.asarray(getattr(pb, f.name))
         for f in dataclasses.fields(pb)}
cfg = KGEConfig(rgcn=RGCNConfig(
    num_entities=kg.num_entities, num_relations=kg.num_relations,
    hidden_dim=16, num_layers=2, num_bases=2, dropout=0.0,
    num_table_shards=2))
params = init_kge_params(jax.random.PRNGKey(0), cfg)
assert params["entity_embedding"].shape[0] == 2
mesh = make_host_mesh(1, 2)                      # data=1 x model=2
opt = adam(0.01)
keys = jax.random.split(jax.random.PRNGKey(2), 1)

step_spmd = make_spmd_train_step(
    lambda p, b, k: fullgraph_loss(p, cfg, b, k, train=False,
                                   model_axis="model"),
    opt, mesh, param_specs=kge_param_specs(params, mesh))
step_sim = make_simulated_train_step(
    lambda p, b, k: fullgraph_loss(p, cfg, b, k, train=False), opt)
# The exchange's REPLICATED-LOSS backward (identity, not the collective
# transpose — sharding.embedding._replicated_exchange) makes the real
# shard_map step BITWISE equal to the vmap simulation: the historical S-x
# entity-gradient inflation (psum transposing to psum under
# check_rep=False, masked by adam's scale-invariant first step and the
# old atol=5e-3) would fail this exactly.
p1, o1, m1 = step_spmd(params, opt.init(params), batch, keys)
p2, o2, m2 = step_sim(params, opt.init(params), batch, keys)
assert float(m1["loss"]) == float(m2["loss"])
for a, b in zip(jax.tree_util.tree_leaves(p1),
                jax.tree_util.tree_leaves(p2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# second step (optimizer state now differs from init — a wrong exchange
# backward would compound here) stays bitwise on the same trajectory
keys2 = jax.random.split(jax.random.PRNGKey(5), 1)
_, _, m1b = step_spmd(p1, o1, batch, keys2)
_, _, m2b = step_sim(p2, o2, batch, keys2)
assert float(m1b["loss"]) == float(m2b["loss"])
assert float(m1b["loss"]) < float(m1["loss"])    # it is actually learning

# every exchange layout over the REAL 2-device axis is bitwise equal to
# the dense replicated psum: same loss, same updated params, bit for bit
ref_p = ref_m = None
for exchange in ("psum", "psum_scatter", "alltoall"):
    cfg_x = KGEConfig(rgcn=dataclasses.replace(
        cfg.rgcn, gather_exchange=exchange))
    step_x = make_spmd_train_step(
        lambda p, b, k: fullgraph_loss(p, cfg_x, b, k, train=False,
                                       model_axis="model"),
        opt, mesh, param_specs=kge_param_specs(params, mesh))
    p_x, _, m_x = step_x(params, opt.init(params), batch, keys)
    if ref_p is None:
        ref_p, ref_m = p_x, m_x
    else:
        assert float(m_x["loss"]) == float(ref_m["loss"]), exchange
        for a, b in zip(jax.tree_util.tree_leaves(p_x),
                        jax.tree_util.tree_leaves(ref_p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("TWO_DEVICE_OK")
"""


@pytest.mark.slow
def test_spmd_two_device_model_axis_psum_exchange():
    """Drive the REAL exchange: 2 forced host devices, mesh 1x2
    (data x model), entity table sharded P('model') so each device holds
    one row block and sharded_gather takes the axis_index + psum branch;
    loss and training trajectory must be BITWISE equal to the
    single-device vmap simulation (the replicated-loss identity backward
    makes the exchange transpose exact)."""
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _TWO_DEVICE_SCRIPT], cwd=repo, env=env,
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "TWO_DEVICE_OK" in proc.stdout


_TWO_DEVICE_INT8_SCRIPT = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 2, jax.devices()
from repro.core import expand_all, make_synthetic_kg, pad_partitions, \\
    partition_graph
from repro.launch.mesh import make_host_mesh
from repro.models import KGEConfig, RGCNConfig, fullgraph_loss, \\
    init_kge_params
from repro.sharding import kge_param_specs
from repro.training import adam
from repro.training.distributed import (
    make_simulated_train_step, make_spmd_train_step,
)

kg = make_synthetic_kg(150, 6, 1200, seed=1).with_inverse_relations()
parts = expand_all(kg, partition_graph(kg, 1, "vertex_cut", seed=0), 2)
pb = pad_partitions(parts)
batch = {f.name: jnp.asarray(getattr(pb, f.name))
         for f in dataclasses.fields(pb)}
cfg = KGEConfig(rgcn=RGCNConfig(
    num_entities=kg.num_entities, num_relations=kg.num_relations,
    hidden_dim=16, num_layers=2, num_bases=2, dropout=0.0,
    num_table_shards=2, table_dtype="int8"))
params = init_kge_params(jax.random.PRNGKey(0), cfg)
assert params["entity_embedding"].shape[0] == 2
assert params["entity_embedding"].dtype == jnp.float32   # fp32 master
mesh = make_host_mesh(1, 2)                      # data=1 x model=2
opt = adam(0.01)
keys = jax.random.split(jax.random.PRNGKey(2), 1)

# the REAL quantized exchange (int8 codes + f32 scale sidecar over the
# 2-device model axis) must be bitwise equal to the single-device int8
# simulation: same loss, same updated fp32 master, two steps deep
step_spmd = make_spmd_train_step(
    lambda p, b, k: fullgraph_loss(p, cfg, b, k, train=False,
                                   model_axis="model"),
    opt, mesh, param_specs=kge_param_specs(params, mesh))
step_sim = make_simulated_train_step(
    lambda p, b, k: fullgraph_loss(p, cfg, b, k, train=False), opt)
p1, o1, m1 = step_spmd(params, opt.init(params), batch, keys)
p2, o2, m2 = step_sim(params, opt.init(params), batch, keys)
assert float(m1["loss"]) == float(m2["loss"])
for a, b in zip(jax.tree_util.tree_leaves(p1),
                jax.tree_util.tree_leaves(p2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
keys2 = jax.random.split(jax.random.PRNGKey(5), 1)
_, _, m1b = step_spmd(p1, o1, batch, keys2)
_, _, m2b = step_sim(p2, o2, batch, keys2)
assert float(m1b["loss"]) == float(m2b["loss"])
assert float(m1b["loss"]) < float(m1["loss"])    # it is actually learning

# every exchange layout carries the int8 codes + scales bitwise equal
ref_p = ref_m = None
for exchange in ("psum", "psum_scatter", "alltoall"):
    cfg_x = KGEConfig(rgcn=dataclasses.replace(
        cfg.rgcn, gather_exchange=exchange))
    step_x = make_spmd_train_step(
        lambda p, b, k: fullgraph_loss(p, cfg_x, b, k, train=False,
                                       model_axis="model"),
        opt, mesh, param_specs=kge_param_specs(params, mesh))
    p_x, _, m_x = step_x(params, opt.init(params), batch, keys)
    if ref_p is None:
        ref_p, ref_m = p_x, m_x
    else:
        assert float(m_x["loss"]) == float(ref_m["loss"]), exchange
        for a, b in zip(jax.tree_util.tree_leaves(p_x),
                        jax.tree_util.tree_leaves(ref_p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("TWO_DEVICE_INT8_OK")
"""


@pytest.mark.slow
def test_spmd_two_device_int8_table_matches_simulation():
    """The int8 table over a REAL 2-device model axis: each device
    quantizes its fp32 master block in-jit and exchanges int8 codes with
    the f32 scale sidecar; the training trajectory (loss, updated master,
    two steps) must be BITWISE equal to the single-device int8 simulation,
    for every exchange layout."""
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _TWO_DEVICE_INT8_SCRIPT], cwd=repo, env=env,
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "TWO_DEVICE_INT8_OK" in proc.stdout
