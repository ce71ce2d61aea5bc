"""Jit-ready wrappers around the Pallas kernels: padding to block multiples,
gathers that stay in XLA, and de-padding of results.

These are the entry points the model layer uses.  Each kernel is compiled
when lowered for TPU and interpreted elsewhere; where an op has a
bit-identical XLA lowering, platforms other than TPU run that instead
(``repro.kernels.platform``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.kge_score import C_BLOCK, Q_BLOCK, kge_score
from repro.kernels.platform import kernel_or_xla
from repro.kernels.rgcn_message import (
    EDGE_BLOCK, VERTEX_BLOCK, basis_message, segment_sum_onehot,
)
from repro.kernels.sharded_gather import (
    COT_BLOCK, ROW_BLOCK, fused_dequant_gather, fused_gather,
    scatter_add_onehot,
)
from repro.kernels.topk import TOPK_C_BLOCK, TOPK_Q_BLOCK, topk_scores


def _pad_to(x: jax.Array, n: int, axis: int = 0, fill=0) -> jax.Array:
    cur = x.shape[axis]
    if cur == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - cur)
    return jnp.pad(x, pad, constant_values=fill)


def _round_up(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


@jax.custom_vjp
def rgcn_message_basis(
    h: jax.Array,          # (V, d_in) vertex states
    src: jax.Array,        # (E,) heads (segment ids)
    rel: jax.Array,        # (E,) relations
    dst: jax.Array,        # (E,) tails (gather ids)
    edge_mask: jax.Array,  # (E,) bool
    bases: jax.Array,      # (B, d_in, d_out)
    coeffs: jax.Array,     # (R, B)
) -> jax.Array:
    """Fused RGCN message layer: gather → basis message kernel →
    one-hot segment-sum kernel → mean normalize.  Matches
    ``ref.rgcn_message_ref`` / ``models.rgcn.message_passing_ref``.

    Differentiable: forward runs the Pallas kernels; backward runs the VJP of
    the mathematically identical reference formulation (the usual pairing —
    the backward's gather/scatter pattern differs from the forward's and is
    left to XLA until profiled as a bottleneck)."""
    return _rgcn_message_basis_fwd_impl(
        h, src, rel, dst, edge_mask, bases, coeffs)


def _rgcn_message_basis_fwd_impl(
    h, src, rel, dst, edge_mask, bases, coeffs,
    interpret: Optional[bool] = None,
) -> jax.Array:
    v, d_in = h.shape
    e = src.shape[0]
    d_out = bases.shape[-1]

    e_pad = _round_up(e, EDGE_BLOCK)
    v_pad = _round_up(v, VERTEX_BLOCK)

    dst_p = _pad_to(dst.astype(jnp.int32), e_pad)
    rel_p = _pad_to(rel.astype(jnp.int32), e_pad)
    src_p = _pad_to(src.astype(jnp.int32), e_pad)
    mask_p = _pad_to(edge_mask.astype(jnp.bool_), e_pad, fill=False)

    h_t = h[dst_p]                     # (E_pad, d_in) XLA gather
    coef = coeffs[rel_p]               # (E_pad, B)
    msg = basis_message(h_t, coef, bases, mask_p, interpret=interpret)
    agg, deg = segment_sum_onehot(
        msg, src_p, mask_p, v_pad, interpret=interpret)
    out = agg[:v] / jnp.maximum(deg[:v], 1.0)
    return out.astype(h.dtype)


def _rgcn_fwd(h, src, rel, dst, edge_mask, bases, coeffs):
    out = _rgcn_message_basis_fwd_impl(
        h, src, rel, dst, edge_mask, bases, coeffs)
    return out, (h, src, rel, dst, edge_mask, bases, coeffs)


def _rgcn_bwd(res, g):
    from repro.kernels import ref
    h, src, rel, dst, edge_mask, bases, coeffs = res
    _, vjp = jax.vjp(
        lambda h_, bases_, coeffs_: ref.rgcn_message_ref(
            h_, src, rel, dst, edge_mask, bases_, coeffs_),
        h, bases, coeffs)
    dh, dbases, dcoeffs = vjp(g)
    return dh, None, None, None, None, dbases, dcoeffs


rgcn_message_basis.defvjp(_rgcn_fwd, _rgcn_bwd)


@functools.partial(jax.jit, static_argnames=("epilogue", "interpret"))
def kge_score_padded(
    q: jax.Array,           # (B, d) prepared query rows
    candidates: jax.Array,  # (C, d) prepared candidate rows
    bias: Optional[jax.Array] = None,   # (B, C) POST-epilogue mask
    q_bias: Optional[jax.Array] = None,  # (B,) pre-epilogue query bias
    c_bias: Optional[jax.Array] = None,  # (C,) pre-epilogue candidate bias
    *, epilogue: str = "bilinear",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Block-padding wrapper around the Pallas ``kge_score`` kernel.

    Takes the canonical decoder query form (``repro.models.decoders``):
    ``epilogue(q @ candidates^T + q_bias + c_bias) + bias``.  ``kge_score``
    asserts B and C are multiples of its 128-row tiles; this wrapper pads
    ragged shapes (the last test batch, a shard's row block) up to the tiles
    and slices the result back to ``(B, C)``.  Pad *candidate* rows get
    post-epilogue bias ``-inf``, so any padded score is ``-inf`` and can
    never outrank (or tie) a real candidate — rank counting over a padded
    score matrix stays exact.  Matches ``kernels.ref.kge_score_ref`` on the
    real rows.
    """
    b, d = q.shape
    c = candidates.shape[0]
    b_pad = _round_up(b, Q_BLOCK)
    c_pad = _round_up(c, C_BLOCK)

    q_p = _pad_to(q, b_pad)
    cand_p = _pad_to(candidates, c_pad)
    if bias is None:
        bias = jnp.zeros((b, c), q.dtype)
    bias_p = _pad_to(_pad_to(bias, b_pad, axis=0), c_pad, axis=1,
                     fill=-jnp.inf)
    qb = jnp.zeros((b,), jnp.float32) if q_bias is None else q_bias
    cb = jnp.zeros((c,), jnp.float32) if c_bias is None else c_bias
    qb_p = _pad_to(qb.astype(jnp.float32), b_pad).reshape(b_pad, 1)
    cb_p = _pad_to(cb.astype(jnp.float32), c_pad).reshape(1, c_pad)
    out = kge_score(q_p, cand_p, bias_p, qb_p, cb_p, epilogue=epilogue,
                    interpret=interpret)
    return out[:b, :c]


# ---------------------------------------------------------------------- #
# Per-shard top-k + merge (repro.serving hot path)
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("k", "interpret", "use_kernel"))
def topk_padded(
    scores: jax.Array,      # (B, C) score block
    k: int,
    *, interpret: Optional[bool] = None,
    use_kernel: Optional[bool] = None,
):
    """Padding/dispatch wrapper around the Pallas ``topk_scores`` kernel:
    ``(values (B, k), indices (B, k))``, values descending, ties broken
    toward the LOWEST index.  ``k`` must already be clamped to ``[1, C]``
    (the serving layer owns the vocabulary clamp so the request-level
    semantics live in one place); ragged B is padded to the kernel's
    128-row tile and sliced back.

    On TPU the Pallas kernel runs; elsewhere the production path is
    ``jax.lax.top_k`` — the same documented selection order (descending,
    lower index wins ties), with no arithmetic that could drift, so the
    two dispatches are bit-identical (``tests/test_serving.py`` asserts
    kernel == ref == ``lax.top_k``).  On CPU the interpreter's per-grid
    overhead would lose to XLA's native TopK."""
    b, c = scores.shape
    if not 1 <= k <= c:
        raise ValueError(f"k={k} outside [1, C={c}] — clamp before topk")
    scores = scores.astype(jnp.float32)

    def kernel(s):
        c_block = min(TOPK_C_BLOCK, _round_up(c, 128))
        s = _pad_to(_pad_to(s, _round_up(b, TOPK_Q_BLOCK)),
                    _round_up(c, c_block), axis=1)
        vals, idx = topk_scores(s, k, num_cols=c, c_block=c_block,
                                interpret=interpret)
        return vals[:b], idx[:b]

    return kernel_or_xla(kernel, lambda s: tuple(jax.lax.top_k(s, k)), scores,
                         use_kernel=use_kernel)


def merge_topk(
    vals: jax.Array,       # (B, S * k) per-shard top-k values, concat
    ids: jax.Array,        # (B, S * k) matching GLOBAL candidate ids
    k: int,
    *, interpret: Optional[bool] = None,
):
    """Global k-way merge of per-shard top-k winners: top-k over the
    concatenated ``(B, S·k)`` value rows, then the winning positions are
    mapped back to their global candidate ids.

    Exactness: each shard's list is (value desc, local index asc) and the
    shard row blocks cover contiguous ascending global-id ranges, so among
    equal values a lower concat POSITION is always a lower global id —
    the lowest-index tie-break of ``topk_padded`` therefore selects and
    orders exactly the candidates dense ``jax.lax.top_k`` would over the
    full axis."""
    v, pos = topk_padded(vals, k, interpret=interpret)
    return v, jnp.take_along_axis(ids, pos, axis=1)


# ---------------------------------------------------------------------- #
# Fused sharded-table gather (repro.sharding.embedding hot path)
# ---------------------------------------------------------------------- #
def flat_gather_plan(local_ids: jax.Array, owned: jax.Array,
                     rows_per_shard: int):
    """Collapse a per-shard gather plan into flat row indices.

    ``(local_ids, owned)`` are the ``(S, V)`` plan of
    ``repro.sharding.embedding.plan_local_gather``; exactly one shard owns
    each valid slot, so the exchange's mask+accumulate reduces to integer
    bookkeeping: ``flat[v] = Σ_s owned[s,v] ? s·rows + local[s,v] : 0`` —
    which is the slot's GLOBAL row id in the stacked ``(S·rows, d)`` table
    — plus ``any_owned[v]`` marking slots no shard owns (dedup-plan
    padding), which must gather exact zeros."""
    s = local_ids.shape[0]
    offsets = (jnp.arange(s, dtype=jnp.int32) * rows_per_shard
               ).reshape((s,) + (1,) * (local_ids.ndim - 1))
    flat = jnp.sum(jnp.where(owned, local_ids.astype(jnp.int32) + offsets,
                             0), axis=0)
    return flat, jnp.any(owned, axis=0)


def _fused_sharded_gather_impl(table, local_ids, owned,
                               interpret: Optional[bool] = None,
                               use_kernel: Optional[bool] = None):
    s, rows, d = table.shape
    flat, any_owned = flat_gather_plan(local_ids, owned, rows)
    # the per-row-DMA kernel on TPU; elsewhere the interpreter's
    # per-grid-step overhead would swamp the gather, so the production
    # path is the IDENTICAL XLA lowering (one masked row gather —
    # tests/test_kernels.py asserts kernel == XLA bit-for-bit)
    return kernel_or_xla(
        functools.partial(fused_gather, interpret=interpret),
        lambda t, f, o: jnp.where(o[:, None], t[f], 0.0),
        table.reshape(s * rows, d), flat, any_owned, use_kernel=use_kernel)


@jax.custom_vjp
def fused_sharded_gather(
    table: jax.Array,      # (S, rows, d) row-sharded table stack
    local_ids: jax.Array,  # (S, V) per-shard LOCAL row ids
    owned: jax.Array,      # (S, V) ownership masks
) -> jax.Array:
    """Fused replacement for the shard-local take → mask → sum chain
    (``ref.sharded_gather_ref``): the ownership masks fold into flat row
    indices (``flat_gather_plan``) and the whole exchange becomes ONE
    masked row gather — V·d elements touched instead of S·V·d, no
    (S, V, d) intermediate.  Bitwise equal to the chain (each output
    element is the owner's row value; the chain adds S−1 zeros to it).

    Differentiable with a fused backward: the custom VJP scatter-adds the
    cotangents straight into the stacked table rows — the SAME single
    scatter-add a dense ``table[ids]`` gather's VJP performs (so sharded
    gradients stay bitwise equal to dense ones) instead of
    differentiating through the S-way mask/sum chain.  On TPU the
    forward runs the ``sharded_gather.fused_gather`` Pallas kernel and
    the backward the ``scatter_add_onehot`` MXU one-hot kernel."""
    return _fused_sharded_gather_impl(table, local_ids, owned)


def _fsg_fwd(table, local_ids, owned):
    out = _fused_sharded_gather_impl(table, local_ids, owned)
    # bwd needs only table's STATIC shape/dtype; the array residual is a
    # free edge to the parameter under jit (no extra buffer)
    return out, (local_ids, owned, table)


def _fsg_bwd(res, g):
    from repro.kernels import ref
    local_ids, owned, table = res
    s, rows, d = table.shape
    dtype = table.dtype
    flat, any_owned = flat_gather_plan(local_ids, owned, rows)

    def kernel(g, flat, any_owned):
        v_pad = _round_up(flat.shape[0], COT_BLOCK)
        r_pad = _round_up(s * rows, ROW_BLOCK)
        return scatter_add_onehot(
            _pad_to(g, v_pad), _pad_to(flat, v_pad),
            _pad_to(any_owned, v_pad, fill=False), r_pad)[:s * rows]

    dt = kernel_or_xla(
        kernel, functools.partial(ref.sharded_scatter_add_ref,
                                  num_rows=s * rows),
        g, flat, any_owned)
    return dt.reshape(s, rows, d).astype(dtype), None, None


fused_sharded_gather.defvjp(_fsg_fwd, _fsg_bwd)

# Public alias: the quantized gathers reuse the SAME straight-through
# backward (scatter-add of the output cotangents into the fp32 master
# table), so master-weight gradients stay bitwise equal to the fp32
# path's gradients on identical dequantized inputs.
fsg_bwd = _fsg_bwd


# ---------------------------------------------------------------------- #
# Quantized (int8) sharded gather — fused dequant variants
# ---------------------------------------------------------------------- #
def dequant_sharded_gather(
    codes: jax.Array,       # (S, rows, d) int8 row codes
    scales: jax.Array,      # (S, rows) fp32 per-row scales
    local_ids: jax.Array,   # (S, V) per-shard LOCAL row ids
    owned: jax.Array,       # (S, V) ownership masks
    interpret: Optional[bool] = None,
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """Fused dequantizing gather over int8 row codes:
    ``out[v] = any_owned[v] ? codes_flat[flat[v]].astype(f32) ·
    scales_flat[flat[v]] : 0`` — the int8 twin of
    :func:`fused_sharded_gather`'s forward.  Only the V gathered rows are
    ever dequantized; no fp32 ``(S·rows, d)`` table exists at any point
    (the replication audit asserts this on the compiled HLO).  On TPU the
    ``sharded_gather.fused_dequant_gather`` Pallas kernel runs; elsewhere
    the identical XLA lowering.  Oracle: ``ref.dequant_gather_ref``
    (dequantize-then-gather), bitwise equal because ``code · scale`` is
    computed in f32 either side of the gather."""
    s, rows, d = codes.shape
    flat, any_owned = flat_gather_plan(local_ids, owned, rows)

    def xla(codes_flat, scales_flat, flat, any_owned):
        rows_f32 = (codes_flat[flat].astype(jnp.float32)
                    * scales_flat[flat][:, None])
        return jnp.where(any_owned[:, None], rows_f32, 0.0)

    return kernel_or_xla(
        functools.partial(fused_dequant_gather, interpret=interpret), xla,
        codes.reshape(s * rows, d), scales.reshape(s * rows), flat,
        any_owned, use_kernel=use_kernel)


def _qsg_impl(table, local_ids, owned):
    # function-level import: sharding.embedding imports this module
    from repro.sharding.embedding import quantize_rows
    codes, scales = quantize_rows(table)
    return dequant_sharded_gather(codes, scales, local_ids, owned)


@jax.custom_vjp
def quantized_sharded_gather(
    table: jax.Array,      # (S, rows, d) fp32 MASTER table stack
    local_ids: jax.Array,  # (S, V) per-shard LOCAL row ids
    owned: jax.Array,      # (S, V) ownership masks
) -> jax.Array:
    """int8 training gather: quantize the fp32 master table row-wise
    in-program, then run the fused dequantizing gather — the optimizer
    only ever sees the fp32 master.  Straight-through custom VJP: the
    backward is :func:`fused_sharded_gather`'s scatter-add (``fsg_bwd``),
    accumulating fp32 cotangents into the master rows, NOT the
    zero-almost-everywhere derivative of round().  Consequence tested in
    tests/test_sharded_embedding.py: master gradients are bitwise equal
    to fp32-path gradients when the fp32 path runs on the dequantized
    master."""
    return _qsg_impl(table, local_ids, owned)


def _qsg_fwd(table, local_ids, owned):
    return _qsg_impl(table, local_ids, owned), (local_ids, owned, table)


quantized_sharded_gather.defvjp(_qsg_fwd, _fsg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def wkv_chunked_op(
    r: jax.Array, k: jax.Array, v: jax.Array, log_decay: jax.Array,
    u: jax.Array, chunk: int = 64,
) -> jax.Array:
    """Padded wrapper for the chunked-WKV Pallas kernel: (BH, S, hd) →
    (BH, S, hd); pads BH to BH_BLOCK and S to the chunk size.
    Differentiable: forward = Pallas kernel, backward = VJP of the
    mathematically identical sequential reference (same pairing as
    ``rgcn_message_basis``)."""
    return _wkv_fwd_impl(r, k, v, log_decay, u, chunk)


def _wkv_fwd_impl(r, k, v, log_decay, u, chunk,
                  interpret: Optional[bool] = None) -> jax.Array:
    from repro.kernels.wkv_chunk import BH_BLOCK, wkv_chunked
    bh, s, hd = r.shape
    bh_p = _round_up(bh, BH_BLOCK)
    s_p = _round_up(s, chunk)

    def pad(x, fill=0.0):
        return _pad_to(_pad_to(x, bh_p, axis=0, fill=fill), s_p, axis=1,
                       fill=fill)

    out = wkv_chunked(
        pad(r), pad(k), pad(v), pad(log_decay),
        _pad_to(u, bh_p, axis=0), chunk=chunk, interpret=interpret)
    return out[:bh, :s]


def _wkv_fwd(r, k, v, log_decay, u, chunk):
    # custom_vjp fwd receives args in primal order; nondiff args go FIRST
    # only in the bwd rule
    return (_wkv_fwd_impl(r, k, v, log_decay, u, chunk),
            (r, k, v, log_decay, u))


def _wkv_bwd(chunk, res, g):
    from repro.kernels import ref
    r, k, v, log_decay, u = res
    _, vjp = jax.vjp(
        lambda *a: ref.wkv_chunk_ref(*a), r, k, v, log_decay, u)
    return vjp(g)


wkv_chunked_op.defvjp(_wkv_fwd, _wkv_bwd)
