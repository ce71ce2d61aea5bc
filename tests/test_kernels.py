"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.rgcn_message import basis_message, segment_sum_onehot


def _mk(rng, v, e, d_in, d_out, nb, r, dtype=np.float32):
    return dict(
        h=jnp.asarray(rng.normal(size=(v, d_in)), dtype),
        src=jnp.asarray(rng.integers(0, v, e), jnp.int32),
        rel=jnp.asarray(rng.integers(0, r, e), jnp.int32),
        dst=jnp.asarray(rng.integers(0, v, e), jnp.int32),
        mask=jnp.asarray(rng.random(e) > 0.15),
        bases=jnp.asarray(rng.normal(size=(nb, d_in, d_out)) * 0.2, dtype),
        coeffs=jnp.asarray(rng.normal(size=(r, nb)), dtype),
    )


SHAPES = [
    (64, 200, 16, 16, 2, 5),
    (128, 512, 32, 48, 3, 11),
    (300, 1024, 75, 75, 2, 474),    # paper's FB15k-237 dims (2×237 rels)
    (33, 129, 8, 8, 1, 2),          # non-aligned
]


@pytest.mark.parametrize("v,e,d_in,d_out,nb,r", SHAPES)
def test_rgcn_kernel_allclose(v, e, d_in, d_out, nb, r):
    rng = np.random.default_rng(v + e)
    a = _mk(rng, v, e, d_in, d_out, nb, r)
    got = ops.rgcn_message_basis(a["h"], a["src"], a["rel"], a["dst"],
                                 a["mask"], a["bases"], a["coeffs"])
    want = ref.rgcn_message_ref(a["h"], a["src"], a["rel"], a["dst"],
                                a["mask"], a["bases"], a["coeffs"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_rgcn_kernel_grads_match_ref():
    rng = np.random.default_rng(3)
    a = _mk(rng, 50, 150, 16, 16, 2, 4)

    def f_kernel(h, bases, coeffs):
        return ops.rgcn_message_basis(
            h, a["src"], a["rel"], a["dst"], a["mask"], bases, coeffs).sum()

    def f_ref(h, bases, coeffs):
        return ref.rgcn_message_ref(
            h, a["src"], a["rel"], a["dst"], a["mask"], bases, coeffs).sum()

    gk = jax.grad(f_kernel, (0, 1, 2))(a["h"], a["bases"], a["coeffs"])
    gr = jax.grad(f_ref, (0, 1, 2))(a["h"], a["bases"], a["coeffs"])
    for x, y in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(
    v=st.integers(8, 200), e=st.integers(8, 600),
    d=st.sampled_from([8, 16, 32, 75]), nb=st.integers(1, 3),
    r=st.integers(1, 12), seed=st.integers(0, 99),
)
def test_property_rgcn_kernel(v, e, d, nb, r, seed):
    rng = np.random.default_rng(seed)
    a = _mk(rng, v, e, d, d, nb, r)
    got = ops.rgcn_message_basis(a["h"], a["src"], a["rel"], a["dst"],
                                 a["mask"], a["bases"], a["coeffs"])
    want = ref.rgcn_message_ref(a["h"], a["src"], a["rel"], a["dst"],
                                a["mask"], a["bases"], a["coeffs"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_basis_message_bf16():
    rng = np.random.default_rng(0)
    e, d, nb = 256, 32, 2
    h_t = jnp.asarray(rng.normal(size=(e, d)), jnp.bfloat16)
    coef = jnp.asarray(rng.normal(size=(e, nb)), jnp.bfloat16)
    bases = jnp.asarray(rng.normal(size=(nb, d, d)) * 0.1, jnp.bfloat16)
    mask = jnp.ones(e, bool)
    got = basis_message(h_t, coef, bases, mask)
    want = ref.basis_message_ref(h_t.astype(jnp.float32),
                                 coef.astype(jnp.float32),
                                 bases.astype(jnp.float32), mask)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_segment_sum_sorted_and_unsorted():
    rng = np.random.default_rng(1)
    e, v, d = 512, 256, 16
    msg = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, v, e), jnp.int32)
    mask = jnp.asarray(rng.random(e) > 0.2)
    for s in (seg, jnp.sort(seg)):
        agg, deg = segment_sum_onehot(msg, s, mask, v)
        wagg, wdeg = ref.segment_mean_ref(msg, s, mask, v)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(wagg),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(deg[:, 0]),
                                   np.asarray(wdeg), rtol=1e-6, atol=0)


KGE_SHAPES = [(32, 100, 16), (128, 1000, 76), (200, 333, 32), (1, 128, 64)]


@pytest.mark.parametrize("b,c,d", KGE_SHAPES)
def test_kge_score_query_form_allclose(b, c, d):
    """Raw query-form kernel vs oracle, both epilogue families.

    ``neg_l2`` gets the decoders' norm-expansion inputs (``q = -2u``,
    ``q_bias = |u|^2``, ``c_bias = |c|^2``, so the pre-epilogue value is
    ``|u - c|^2 >= 0``), as TransE and RotatE feed it.  Raw random query
    and bias rows put that value near 0, where ``-sqrt`` scales an fp32
    summation-order difference ``e`` up to ``e / (2 sqrt(x))`` without
    bound — a property of the inputs, not of the kernel."""
    from repro.kernels.kge_score import EPILOGUES
    rng = np.random.default_rng(b * c)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    cand = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)
    qb = jnp.asarray(rng.random(b), jnp.float32)
    cb = jnp.asarray(rng.random(c), jnp.float32)
    bias = jnp.asarray(
        np.where(rng.random((b, c)) < 0.1, -1e9, 0.0), jnp.float32)
    norm_form = {"bilinear": (q, qb, cb),
                 "neg_l2": (-2.0 * q, jnp.sum(q * q, axis=1),
                            jnp.sum(cand * cand, axis=1))}
    for epi in EPILOGUES:
        q, qb, cb = norm_form[epi]
        got = ops.kge_score_padded(q, cand, bias, qb, cb, epilogue=epi)
        want = ref.kge_score_ref(q, cand, bias, qb, cb, epilogue=epi)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_kge_score_no_bias():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
    cand = jnp.asarray(rng.normal(size=(50, 8)), jnp.float32)
    got = ops.kge_score_padded(q, cand)
    want = ref.kge_score_ref(q, cand)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,c,d", [(32, 100, 16), (130, 280, 24)])
def test_kge_rank_scores_every_decoder(b, c, d):
    """Decoder.rank_scores (Pallas) vs score_against_candidates (XLA) for
    every registered decoder — a decoder silently dropping off the kernel
    path fails here before it fails the bench gate."""
    from repro.models.decoders import (
        init_decoder_params, registered_decoders, get_decoder,
        score_against_candidates,
    )
    rng = np.random.default_rng(b + c)
    h = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    rel = jnp.asarray(rng.integers(0, 7, b), jnp.int32)
    cand = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)
    bias = jnp.asarray(
        np.where(rng.random((b, c)) < 0.1, -1e9, 0.0), jnp.float32)
    for name in registered_decoders():
        dec = get_decoder(name)
        p = init_decoder_params(jax.random.PRNGKey(3), name, 7, d)
        got = dec.rank_scores(p, h, rel, cand, bias)
        want = score_against_candidates(p, name, h, rel, cand, bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


# ---------------------------------------------------------------------- #
# Chunked WKV kernel (RWKV-6 time-mix core)
# ---------------------------------------------------------------------- #
WKV_SHAPES = [(8, 64, 16, 16), (16, 128, 32, 32), (3, 50, 8, 16),
              (8, 64, 64, 64)]


@pytest.mark.parametrize("bh,s,hd,chunk", WKV_SHAPES)
def test_wkv_kernel_allclose(bh, s, hd, chunk):
    from repro.kernels.ops import wkv_chunked_op
    from repro.kernels.ref import wkv_chunk_ref
    rng = np.random.default_rng(bh * s)
    r = jnp.asarray(rng.normal(size=(bh, s, hd)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(bh, s, hd)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(bh, s, hd)) * 0.5, jnp.float32)
    lw = jnp.asarray(-np.exp(rng.normal(size=(bh, s, hd)) * 0.3 - 3),
                     jnp.float32)
    u = jnp.asarray(rng.normal(size=(bh, hd)) * 0.1, jnp.float32)
    got = wkv_chunked_op(r, k, v, lw, u, chunk=chunk)
    want = wkv_chunk_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(bh=st.integers(1, 12), s=st.integers(4, 80),
       hd=st.sampled_from([8, 16]), seed=st.integers(0, 50))
def test_property_wkv_kernel(bh, s, hd, seed):
    from repro.kernels.ops import wkv_chunked_op
    from repro.kernels.ref import wkv_chunk_ref
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.normal(size=(bh, s, hd)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(bh, s, hd)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(bh, s, hd)) * 0.3, jnp.float32)
    lw = jnp.asarray(-np.exp(rng.normal(size=(bh, s, hd)) * 0.2 - 3),
                     jnp.float32)
    u = jnp.asarray(rng.normal(size=(bh, hd)) * 0.1, jnp.float32)
    got = wkv_chunked_op(r, k, v, lw, u, chunk=16)
    want = wkv_chunk_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ====================================================================== #
# Fused sharded-table gather (sharded_gather.py / ops.fused_sharded_gather)
# ====================================================================== #
def _sharded_setup(rng, n, d, s, v):
    from repro.sharding.embedding import (
        ShardedTableLayout, plan_local_gather, shard_table,
    )
    dense = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    lay = ShardedTableLayout(n, s)
    table = shard_table(dense, lay)
    ids = np.asarray(rng.integers(0, n, v), np.int32)
    local, owned = plan_local_gather(lay, ids)
    return dense, lay, table, ids, jnp.asarray(local), jnp.asarray(owned)


@pytest.mark.parametrize("s,n,d,v", [
    (1, 256, 8, 128), (2, 256, 8, 128), (4, 300, 16, 256),
])
def test_fused_gather_kernel_bitwise_vs_xla_and_ref(s, n, d, v):
    """The Pallas gather kernel (interpret), the XLA lowering the CPU path
    uses, and the original take->mask->sum chain all agree BITWISE."""
    from repro.kernels.sharded_gather import fused_gather
    rng = np.random.default_rng(s * n)
    dense, lay, table, ids, local, owned = _sharded_setup(rng, n, d, s, v)
    flat, anyo = ops.flat_gather_plan(local, owned, lay.rows_per_shard)
    flat_table = table.reshape(-1, d)
    kern = fused_gather(flat_table, flat, anyo, interpret=True)
    xla = jnp.where(anyo[:, None], flat_table[flat], 0.0)
    chain = ref.sharded_gather_ref(table, local, owned)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(chain))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(dense[ids]))


def test_fused_gather_kernel_masks_unowned_rows():
    """Dedup-plan padding: slots no shard owns must gather exact zeros."""
    from repro.kernels.sharded_gather import fused_gather
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    flat = jnp.asarray([3, 0, 5, 0], jnp.int32)
    anyo = jnp.asarray([True, False, True, False])
    out = np.asarray(fused_gather(table, flat, anyo, interpret=True))
    np.testing.assert_array_equal(out[0], np.asarray(table[3]))
    np.testing.assert_array_equal(out[2], np.asarray(table[5]))
    assert (out[1] == 0).all() and (out[3] == 0).all()


@pytest.mark.parametrize("s,n,d,v", [(2, 256, 8, 128), (4, 256, 16, 256)])
def test_scatter_add_kernel_matches_ref(s, n, d, v):
    from repro.kernels.sharded_gather import scatter_add_onehot
    rng = np.random.default_rng(s + v)
    _, lay, _, _, local, owned = _sharded_setup(rng, n, d, s, v)
    flat, anyo = ops.flat_gather_plan(local, owned, lay.rows_per_shard)
    g = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    got = scatter_add_onehot(g, flat, anyo, lay.padded_rows, interpret=True)
    want = ref.sharded_scatter_add_ref(g, flat, anyo, lay.padded_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_fused_sharded_gather_grads_bitwise_vs_dense():
    """The custom VJP performs the SAME single scatter-add as the dense
    gather's VJP — gradients are bitwise equal, duplicates included."""
    from repro.sharding.embedding import unshard_table
    rng = np.random.default_rng(11)
    n, d, s = 300, 16, 4
    dense, lay, table, _, _, _ = _sharded_setup(rng, n, d, s, 8)
    ids = np.asarray([7, 7, 7, 0, n - 1, 7, 0, 5], np.int32)  # heavy dups
    from repro.sharding.embedding import plan_local_gather
    local, owned = plan_local_gather(lay, ids)
    local, owned = jnp.asarray(local), jnp.asarray(owned)
    w = jnp.arange(1.0, d + 1)
    g_sh = jax.grad(lambda t: jnp.sum(jnp.tanh(
        ops.fused_sharded_gather(t, local, owned)) * w))(table)
    g_d = jax.grad(lambda t: jnp.sum(jnp.tanh(t[ids]) * w))(dense)
    np.testing.assert_array_equal(
        np.asarray(unshard_table(g_sh, n)), np.asarray(g_d))
