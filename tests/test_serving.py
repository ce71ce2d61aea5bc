"""Serving subsystem tests: the sharded top-k contract, filtered serving,
dynamic batching integrity, and the truncation / k-clamp regressions.

The load-bearing gate is EXACT equality (``==``, not allclose) between the
sharded per-shard-topk + merge path and dense ``jax.lax.top_k`` — the
sharded path never materializes the dense score matrix, so bit-exactness
is the only evidence it computes the same answer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import KnowledgeGraph
from repro.eval.ranking import (
    CSRFilterIndex, _filter_bias, build_filter_index,
)
from repro.kernels.ops import merge_topk, topk_padded
from repro.kernels.ref import topk_ref
from repro.kernels.topk import topk_scores
from repro.models.decoders import (
    init_decoder_params, registered_decoders, score_against_candidates,
)
from repro.serving import (
    KGEServeEngine, KGEServer, Request, ServeEngine,
    ShardedKGEServer,
)

N_ENT, DIM, N_REL = 57, 8, 3


@pytest.fixture(scope="module")
def emb():
    rng = np.random.default_rng(0)
    e = rng.normal(size=(N_ENT, DIM)).astype(np.float32)
    e[7] = e[19]          # exact duplicate rows -> exact score ties
    e[40] = e[19]
    return e


@pytest.fixture(scope="module")
def graph(emb):
    rng = np.random.default_rng(1)
    return KnowledgeGraph(
        src=rng.integers(0, N_ENT, 400), rel=rng.integers(0, N_REL, 400),
        dst=rng.integers(0, N_ENT, 400), num_entities=N_ENT,
        num_relations=N_REL)


def dense_topk(emb, params, decoder, heads, rels, k, filter_index=None):
    """Dense oracle with serving filter semantics (every known tail of
    (h, r) masked — sentinel t = -1, no held-out true tail)."""
    scores = np.asarray(score_against_candidates(
        params, decoder, jnp.asarray(emb[heads]),
        jnp.asarray(np.asarray(rels).astype(np.int32)), jnp.asarray(emb)))
    if filter_index is not None:
        batch = np.stack(
            [np.asarray(heads, np.int64), np.asarray(rels, np.int64),
             np.full(len(heads), -1, np.int64)], axis=1)
        scores = scores + _filter_bias(filter_index, batch, emb.shape[0])
    v, i = jax.lax.top_k(jnp.asarray(scores), k)
    return np.asarray(v), np.asarray(i)


# ---------------------------------------------------------------------- #
# top-k kernel parity
# ---------------------------------------------------------------------- #
class TestTopkKernel:
    @pytest.mark.parametrize("k", [1, 3, 17])
    def test_kernel_ref_lax_agree(self, k):
        """Pallas kernel == jnp oracle == jax.lax.top_k, values AND
        indices, on tie-heavy data (selection is arithmetic-free)."""
        rng = np.random.default_rng(2)
        scores = rng.normal(size=(5, 40)).astype(np.float32)
        scores[:, 11] = scores[:, 3]       # duplicate columns -> ties
        scores[:, 29] = scores[:, 3]
        scores[2] = 1.0                    # an all-equal row
        s = jnp.asarray(scores)
        kv, ki = topk_padded(s, k, use_kernel=True, interpret=True)
        rv, ri = topk_ref(s, k)
        lv, li = jax.lax.top_k(s, k)
        for got_v, got_i in ((kv, ki), (rv, ri)):
            assert (np.asarray(got_v) == np.asarray(lv)).all()
            assert (np.asarray(got_i) == np.asarray(li)).all()

    def test_neg_inf_rows_drain_in_index_order(self):
        """Repeated -inf entries (filtered/padded candidates) must come
        out in ascending index order like lax.top_k, not loop forever."""
        s = jnp.asarray(np.full((3, 8), -np.inf, np.float32))
        kv, ki = topk_padded(s, 4, use_kernel=True, interpret=True)
        lv, li = jax.lax.top_k(s, 4)
        assert (np.asarray(ki) == np.asarray(li)).all()
        assert np.isneginf(np.asarray(kv)).all()

    @pytest.mark.parametrize("k", [1, 5, 130])
    def test_candidate_tiles_merge_exactly(self, k):
        """A score block cut into 128-column candidate tiles (C = 300:
        three tiles, the last one partly padding) gives the values AND
        indices of one ``lax.top_k`` — with ties and ``-inf`` runs that
        cross tile edges, and with k wider than a tile."""
        rng = np.random.default_rng(5)
        scores = rng.normal(size=(128, 384)).astype(np.float32)
        scores[:, 130] = scores[:, 7]      # ties across tile edges
        scores[:, 299] = scores[:, 7]
        scores[3] = -np.inf                # drains in index order
        scores[4, ::2] = 2.0               # half a row tied at the top
        scores[:, 300:] = 9.0              # padding must never win
        kv, ki = topk_scores(jnp.asarray(scores), k, num_cols=300,
                             c_block=128, interpret=True)
        lv, li = jax.lax.top_k(jnp.asarray(scores[:, :300]), k)
        assert (np.asarray(kv) == np.asarray(lv)).all()
        assert (np.asarray(ki) == np.asarray(li)).all()

    def test_k_out_of_range_raises(self):
        s = jnp.zeros((2, 6), jnp.float32)
        with pytest.raises(ValueError):
            topk_padded(s, 0)
        with pytest.raises(ValueError):
            topk_padded(s, 7)

    def test_merge_topk_tie_break_by_position(self):
        """merge picks the lowest CONCAT position among equal values and
        returns that position's id — the shard-order invariant the global
        merge's exactness rests on."""
        vals = jnp.asarray([[5.0, 1.0, 5.0, 3.0]])
        ids = jnp.asarray([[30, 11, 2, 7]], dtype=jnp.int32)
        mv, mi = merge_topk(vals, ids, 3)
        assert np.asarray(mv).tolist() == [[5.0, 5.0, 3.0]]
        assert np.asarray(mi).tolist() == [[30, 2, 7]]


# ---------------------------------------------------------------------- #
# sharded top-k == dense, per decoder / shard count / filter mode
# ---------------------------------------------------------------------- #
class TestShardedTopk:
    @pytest.mark.parametrize("decoder", registered_decoders())
    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_equal_dense_every_decoder(self, emb, decoder, shards):
        """Sharded per-shard-topk + merge == dense jax.lax.top_k, values
        AND indices, including exact ties and duplicate heads."""
        p = init_decoder_params(jax.random.PRNGKey(0), decoder, N_REL, DIM)
        heads = np.array([0, 7, 19, 19, 50])   # duplicates + tied rows
        rels = np.array([0, 1, 2, 2, 0])
        srv = ShardedKGEServer(emb, p, decoder, num_shards=shards)
        sv, si = srv.topk_tails(heads, rels, 11)
        dv, di = dense_topk(emb, p, decoder, heads, rels, 11)
        assert (si == di).all()
        assert (sv == dv).all()

    def test_k_clamps_to_vocab(self, emb):
        p = init_decoder_params(jax.random.PRNGKey(0), "distmult",
                                N_REL, DIM)
        srv = ShardedKGEServer(emb, p, num_shards=2)
        sv, si = srv.topk_tails(np.array([0]), np.array([0]), k=10 * N_ENT)
        assert si.shape == (1, N_ENT)
        # a full-vocab result is a permutation of all entity ids — layout
        # padding rows never leak out
        assert sorted(si[0].tolist()) == list(range(N_ENT))
        with pytest.raises(ValueError):
            srv.topk_tails(np.array([0]), np.array([0]), k=0)

    @pytest.mark.parametrize("decoder", registered_decoders())
    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_int8_equal_dense_over_dequantized_table(self, emb, decoder,
                                                     shards):
        """``table_dtype="int8"``: only codes + scales live on device and
        the top-k program dequantizes per shard block — values AND indices
        must EXACTLY equal dense top-k over the dequantized table (the
        dequant is one exact power-of-two multiply per element, so the
        sharded and dense paths see bit-identical scores)."""
        from repro.sharding.embedding import dequantize_rows, quantize_rows
        dq = np.asarray(dequantize_rows(*quantize_rows(emb)))
        p = init_decoder_params(jax.random.PRNGKey(0), decoder, N_REL, DIM)
        heads = np.array([0, 7, 19, 19, 50])   # duplicates + tied rows
        rels = np.array([0, 1, 2, 2, 0])
        srv = ShardedKGEServer(emb, p, decoder, num_shards=shards,
                               table_dtype="int8")
        sv, si = srv.topk_tails(heads, rels, 11)
        dv, di = dense_topk(dq, p, decoder, heads, rels, 11)
        assert (si == di).all()
        assert (sv == dv).all()

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_int8_filtered_equal_dense(self, emb, graph, shards):
        """Filtered int8 serving == dense + filter bias over the
        dequantized table, exactly."""
        from repro.sharding.embedding import dequantize_rows, quantize_rows
        dq = np.asarray(dequantize_rows(*quantize_rows(emb)))
        p = init_decoder_params(jax.random.PRNGKey(1), "distmult",
                                N_REL, DIM)
        heads = np.array([0, 3, 7, 19])
        rels = np.array([0, 1, 2, 2])
        csr = CSRFilterIndex.build([graph])
        dv, di = dense_topk(dq, p, "distmult", heads, rels, 9, csr)
        srv = ShardedKGEServer(emb, p, num_shards=shards,
                               filter_index=csr, table_dtype="int8")
        sv, si = srv.topk_tails(heads, rels, 9, filtered=True)
        assert (si == di).all()
        assert (sv == dv).all()

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_filtered_equal_dense_csr_and_dict(self, emb, graph, shards):
        """Filtered serving == dense + serving-sentinel filter bias, for
        both the CSR index and the dict reference form."""
        p = init_decoder_params(jax.random.PRNGKey(1), "distmult",
                                N_REL, DIM)
        heads = np.array([0, 3, 7, 19])
        rels = np.array([0, 1, 2, 2])
        csr = CSRFilterIndex.build([graph])
        ref = build_filter_index([graph])
        dv, di = dense_topk(emb, p, "distmult", heads, rels, 9, csr)
        for idx in (csr, ref):
            srv = ShardedKGEServer(emb, p, num_shards=shards,
                                   filter_index=idx)
            sv, si = srv.topk_tails(heads, rels, 9, filtered=True)
            assert (si == di).all()
            assert (sv == dv).all()

    def test_filtered_masks_all_known_tails(self, emb, graph):
        """Serving has no held-out true tail: EVERY known tail of (h, r)
        must be filtered (the sentinel t = -1 semantics), unlike eval
        which un-filters the row's own tail."""
        p = init_decoder_params(jax.random.PRNGKey(1), "distmult",
                                N_REL, DIM)
        csr = CSRFilterIndex.build([graph])
        h, r = int(graph.src[0]), int(graph.rel[0])
        known = set(csr.tails_of(h, r).tolist())
        assert known, "fixture graph must have known tails for the probe"
        srv = ShardedKGEServer(emb, p, num_shards=2, filter_index=csr)
        _, si = srv.topk_tails(np.array([h]), np.array([r]),
                               k=N_ENT - len(known), filtered=True)
        assert not (set(si[0].tolist()) & known)

    def test_filtered_without_index_raises(self, emb):
        p = init_decoder_params(jax.random.PRNGKey(0), "distmult",
                                N_REL, DIM)
        srv = ShardedKGEServer(emb, p, num_shards=2)
        with pytest.raises(ValueError):
            srv.topk_tails(np.array([0]), np.array([0]), filtered=True)

    def test_head_cache_changes_no_bits(self, emb):
        """The hot-entity LRU only short-circuits the gather exchange —
        results are bitwise identical, and repeats actually hit."""
        p = init_decoder_params(jax.random.PRNGKey(2), "distmult",
                                N_REL, DIM)
        heads = np.array([5, 5, 19, 5])
        rels = np.array([0, 1, 2, 0])
        plain = ShardedKGEServer(emb, p, num_shards=2)
        cached = ShardedKGEServer(emb, p, num_shards=2, cache_size=16)
        for _ in range(2):                    # second round is all hits
            pv, pi = plain.topk_tails(heads, rels, 7)
            cv, ci = cached.topk_tails(heads, rels, 7)
            assert (pi == ci).all() and (pv == cv).all()
        assert cached.cache_hits > 0
        assert len(cached._cache) <= 16

    def test_cache_smaller_than_batch_falls_back(self, emb):
        """A batch with more unique heads than cache entries still answers
        correctly (direct-gather fallback)."""
        p = init_decoder_params(jax.random.PRNGKey(2), "distmult",
                                N_REL, DIM)
        heads = np.arange(8)
        rels = np.zeros(8, np.int64)
        plain = ShardedKGEServer(emb, p, num_shards=2)
        tiny = ShardedKGEServer(emb, p, num_shards=2, cache_size=2)
        pv, pi = plain.topk_tails(heads, rels, 5)
        cv, ci = tiny.topk_tails(heads, rels, 5)
        assert (pi == ci).all() and (pv == cv).all()
        assert len(tiny._cache) <= 2


# ---------------------------------------------------------------------- #
# dynamic batching
# ---------------------------------------------------------------------- #
class TestKGEServeEngine:
    def _server(self, emb, **kw):
        p = init_decoder_params(jax.random.PRNGKey(3), "distmult",
                                N_REL, DIM)
        return ShardedKGEServer(emb, p, num_shards=2, **kw), p

    def test_out_of_order_integrity(self, emb):
        """smallest-k-first admission completes requests out of submission
        order; every response must still equal ITS OWN query's dense
        top-k (integrity by identity, not order)."""
        srv, p = self._server(emb)
        eng = KGEServeEngine(srv, slots=3, max_k=9,
                             policy="smallest-k-first")
        rng = np.random.default_rng(4)
        reqs = [eng.submit(int(h), int(r), k=int(k)) for h, r, k in zip(
            rng.integers(0, N_ENT, 10), rng.integers(0, N_REL, 10),
            rng.integers(1, 10, 10))]
        done = eng.run()
        assert len(done) == 10 and all(r.done for r in reqs)
        order = [r.request_id for r in done]
        assert order != sorted(order), "policy must reorder completion"
        for r in reqs:
            dv, di = dense_topk(emb, p, "distmult", np.array([r.head]),
                                np.array([r.relation]), r.k)
            assert (r.tails == di[0]).all() and (r.scores == dv[0]).all()

    def test_fifo_partial_batches_and_padding(self, emb):
        """Queue sizes that don't divide slots still answer every request
        (pad slots are dropped); per-request k slices the shared max_k."""
        srv, p = self._server(emb)
        eng = KGEServeEngine(srv, slots=4, max_k=8)
        reqs = [eng.submit(i % N_ENT, i % N_REL, k=1 + i % 8)
                for i in range(7)]
        done = eng.run()
        assert [r.request_id for r in done] == \
            [r.request_id for r in reqs]          # FIFO preserves order
        assert eng.pending == 0
        for r in reqs:
            assert r.tails.shape == (r.k,)
            _, di = dense_topk(emb, p, "distmult", np.array([r.head]),
                               np.array([r.relation]), r.k)
            assert (r.tails == di[0]).all()

    def test_k_over_max_k_rejected(self, emb):
        srv, _ = self._server(emb)
        eng = KGEServeEngine(srv, slots=2, max_k=5)
        with pytest.raises(ValueError):
            eng.submit(0, 0, k=6)
        with pytest.raises(ValueError):
            eng.submit(0, 0, k=0)

    def test_unknown_policy_rejected(self, emb):
        srv, _ = self._server(emb)
        with pytest.raises(ValueError):
            KGEServeEngine(srv, policy="largest-first")


# ---------------------------------------------------------------------- #
# regressions: LM truncation honesty + dense KGEServer k guard
# ---------------------------------------------------------------------- #
class TestRegressions:
    def test_lm_truncation_reported(self):
        """A request the max_seq horizon cuts off must NOT claim done —
        the old engine silently reported truncated output as complete."""
        from repro.configs import get_arch
        from repro.nn import init_params
        cfg = get_arch("gemma-2b").reduced()
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        eng = ServeEngine(cfg, params, slots=2, max_seq=8)
        cut = Request(0, np.array([1, 2, 3], np.int32), max_new_tokens=50)
        fits = Request(1, np.array([1, 2], np.int32), max_new_tokens=3)
        eng.run([cut, fits])
        assert cut.truncated and not cut.done
        assert len(cut.output) < cut.max_new_tokens
        assert fits.done and not fits.truncated
        assert len(fits.output) == 3

    def test_dense_kge_server_k_guard(self, emb):
        """k > vocab clamps instead of crashing; ties break toward the
        lowest entity id on every backend; k < 1 raises."""
        p = init_decoder_params(jax.random.PRNGKey(0), "distmult",
                                N_REL, DIM)
        srv = KGEServer(emb, p)
        top = srv.topk_tails(np.array([0, 1]), np.array([0, 1]),
                             k=10 * N_ENT)
        assert top.shape == (2, N_ENT)
        assert sorted(top[0].tolist()) == list(range(N_ENT))
        with pytest.raises(ValueError):
            srv.topk_tails(np.array([0]), np.array([0]), k=0)
        # deterministic ties: entity 7 == 19 == 40 (duplicate rows) must
        # appear in ascending id order whenever they tie
        _, di = dense_topk(emb, p, "distmult", np.array([7]),
                           np.array([0]), N_ENT)
        got = srv.topk_tails(np.array([7]), np.array([0]), k=N_ENT)
        assert (got[0] == di[0]).all()
        tied = [t for t in got[0].tolist() if t in (7, 19, 40)]
        assert tied == sorted(tied)
