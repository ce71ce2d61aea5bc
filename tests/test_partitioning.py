"""Partitioning invariants (paper §3.2) — unit + hypothesis property tests."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    expand_all, expand_partition, load_balance, make_synthetic_kg,
    pad_partitions, partition_graph, replication_factor,
    verify_self_sufficiency,
)


def _cover_and_disjoint(kg, parts):
    ids = np.concatenate([p.core_edge_ids for p in parts])
    return (np.unique(ids).shape[0] == kg.num_edges,
            ids.shape[0] == np.unique(ids).shape[0])


class TestVertexCut:
    def test_disjoint_cover(self, small_kg):
        parts = partition_graph(small_kg, 4, "vertex_cut", seed=0)
        cover, disjoint = _cover_and_disjoint(small_kg, parts)
        assert cover and disjoint

    def test_balance(self, small_kg):
        parts = partition_graph(small_kg, 4, "vertex_cut", seed=0)
        assert load_balance(parts) <= 1.06   # hard cap in the partitioner

    def test_rf_beats_random(self, small_kg):
        """Table 5's core claim: vertex-cut replicates fewer vertices than
        random edge assignment."""
        vc = partition_graph(small_kg, 4, "vertex_cut", seed=0)
        rnd = partition_graph(small_kg, 4, "random", seed=0)
        assert replication_factor(small_kg, vc) < \
            replication_factor(small_kg, rnd)

    def test_single_partition_identity(self, small_kg):
        parts = partition_graph(small_kg, 1, "vertex_cut", seed=0)
        assert parts[0].num_core_edges() == small_kg.num_edges


class TestEdgeCut:
    def test_cover_with_replication(self, small_kg):
        parts = partition_graph(small_kg, 4, "edge_cut", seed=0)
        cover, disjoint = _cover_and_disjoint(small_kg, parts)
        assert cover
        # edge-cut REPLICATES cut edges (the paper's Fig. 4b pathology)
        total = sum(p.num_core_edges() for p in parts)
        assert total >= small_kg.num_edges


class TestExpansion:
    def test_self_sufficiency(self, small_kg, partitioned):
        _, expanded = partitioned
        for sp in expanded:
            assert verify_self_sufficiency(small_kg, sp)

    def test_core_vertices_first(self, partitioned):
        _, expanded = partitioned
        for sp in expanded:
            core = sp.local_to_global[: sp.num_core_vertices]
            # core-edge endpoints must all be core vertices (< boundary)
            ce = sp.core_edges_local()
            assert (ce[:, 0] < sp.num_core_vertices).all()
            assert (ce[:, 2] < sp.num_core_vertices).all()
            assert np.unique(core).shape[0] == core.shape[0]

    def test_expansion_superset(self, small_kg):
        parts = partition_graph(small_kg, 4, "vertex_cut", seed=0)
        exp = expand_all(small_kg, parts, num_hops=2)
        for p, sp in zip(parts, exp):
            assert sp.num_core_edges == p.num_core_edges()
            assert sp.num_local_edges >= sp.num_core_edges

    def test_more_hops_more_support(self, small_kg):
        parts = partition_graph(small_kg, 4, "vertex_cut", seed=0)
        e1 = expand_all(small_kg, parts, num_hops=1)
        e2 = expand_all(small_kg, parts, num_hops=2)
        for a, b in zip(e1, e2):
            assert b.num_local_edges >= a.num_local_edges


class TestPadding:
    def test_padded_shapes_aligned(self, partitioned):
        _, expanded = partitioned
        pb = pad_partitions(expanded)
        assert pb.padded_edges % 128 == 0
        assert pb.src.shape == (4, pb.padded_edges)
        # masked-out slots don't count as core
        assert not (pb.core_edge_mask & ~pb.edge_mask).any()

    def test_roundtrip_content(self, partitioned):
        _, expanded = partitioned
        pb = pad_partitions(expanded)
        for i, sp in enumerate(expanded):
            e = sp.num_local_edges
            assert (pb.src[i, :e] == sp.src).all()
            assert (pb.edge_mask[i, e:] == False).all()  # noqa: E712

    @pytest.mark.parametrize("p", [1, 4])
    def test_core_edge_index(self, small_kg, p):
        """Each row lists its partition's core edge positions, ascending;
        the padded slots lie at or beyond ``num_core_edges``."""
        exp = expand_all(small_kg, partition_graph(
            small_kg, p, "vertex_cut", seed=0), num_hops=2)
        pb = pad_partitions(exp)
        c_max = pb.core_edge_index.shape[1]
        assert pb.core_edge_index.shape == (p, c_max)
        assert pb.core_edge_index.dtype == np.int32
        assert c_max % 128 == 0
        assert c_max - 128 < pb.num_core_edges.max() <= c_max <= \
            pb.padded_edges
        for i in range(p):
            n = int(pb.num_core_edges[i])
            assert n == exp[i].num_core_edges
            np.testing.assert_array_equal(
                pb.core_edge_index[i, :n],
                np.flatnonzero(pb.core_edge_mask[i]))
            assert (pb.core_edge_index[i, n:] == 0).all()
        fill = pb.core_slot_fill()
        assert 0.0 < fill <= 1.0
        assert fill == pb.num_core_edges.sum() / (p * c_max)


@settings(max_examples=15, deadline=None)
@given(
    n_ent=st.integers(30, 120),
    n_edges=st.integers(60, 500),
    p=st.integers(1, 5),
    hops=st.integers(1, 3),
    strategy=st.sampled_from(["vertex_cut", "edge_cut", "random"]),
    seed=st.integers(0, 5),
)
def test_property_partition_expand(n_ent, n_edges, p, hops, strategy, seed):
    """Any strategy × any graph: cover holds and expansion is
    self-sufficient — the paper's central invariant."""
    kg = make_synthetic_kg(n_ent, 4, n_edges, seed=seed) \
        .with_inverse_relations()
    parts = partition_graph(kg, p, strategy, seed=seed)
    ids = np.unique(np.concatenate([q.core_edge_ids for q in parts]))
    assert ids.shape[0] == kg.num_edges
    for i, part in enumerate(parts):
        sp = expand_partition(kg, part, hops, partition_id=i)
        assert verify_self_sufficiency(kg, sp)
        # replication-factor sanity: core vertices ⊆ local vertices
        assert sp.num_core_vertices <= sp.num_local_vertices


def test_replication_factor_bounds(small_kg):
    parts = partition_graph(small_kg, 4, "vertex_cut", seed=0)
    rf = replication_factor(small_kg, parts)
    assert 0.8 <= rf <= 4.0
    # RF normalizes by ALL of |V| (paper Eq. 7); isolated vertices make the
    # 1-partition RF slightly below 1.0
    rf1 = replication_factor(
        small_kg, partition_graph(small_kg, 1, "vertex_cut"))
    non_isolated = (small_kg.degrees() > 0).mean()
    assert rf1 == pytest.approx(float(non_isolated))
    assert rf >= rf1
