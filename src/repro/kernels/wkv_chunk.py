"""Pallas TPU kernel for chunked RWKV-6 WKV (DESIGN.md §6, §8).

The §Perf pass made chunked WKV the dominant RWKV form (330× memory-term win
over the sequential scan); this kernel is its TPU-native realization: the
recurrent state lives in VMEM scratch across the sequential chunk dimension
of the grid — zero HBM state traffic between chunks — and all intra-chunk
work is MXU matmuls.

Math per chunk (exclusive cumulated log-decay L, bonus u):

    out  = tril_strict((r·e^L)(k·e^{-L-logw})^T) v
           + diag(Σ_d r·u·k) v + (r·e^L) S_in
    S'   = e^{L_tot} ⊙ S_in + (k·e^{L_tot-L-logw})^T v

Grid: (num_bh_tiles, num_chunks) with chunks minor (sequential) so the
scratch state persists across a tile's chunks.  Oracle:
``ref.wkv_chunk_ref`` (== the sequential recurrence, tested both ways).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import run_pallas

BH_BLOCK = 8        # batch·head rows per tile (sublane-aligned)


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, out_ref, state_ref):
    """One (BH_BLOCK, chunk, hd) tile; state scratch (BH_BLOCK, hd, hd)."""
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[...].astype(jnp.float32)          # (B, K, hd)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lw = lw_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)          # (B, hd)
    kdim = r.shape[1]

    l_exc = jnp.cumsum(lw, axis=1) - lw         # (B, K, hd)
    l_inc = l_exc + lw
    l_tot = l_inc[:, -1:, :]                    # (B, 1, hd)

    r_t = r * jnp.exp(l_exc)
    k_t = k * jnp.exp(-l_inc)
    scores = jax.lax.dot_general(               # (B, K, K)
        r_t, k_t, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (kdim, kdim), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (kdim, kdim), 1)
    scores = jnp.where((cols < rows)[None], scores, 0.0)
    intra = jax.lax.dot_general(                # (B, K, hd)
        scores, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    bonus = jnp.sum(r * u[:, None, :] * k, axis=-1, keepdims=True)
    state = state_ref[...]
    cross = jax.lax.dot_general(                # (B, K, hd_v)
        r_t, state, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    out_ref[...] = (intra + bonus * v + cross).astype(out_ref.dtype)

    k_out = k * jnp.exp(l_tot - l_inc)          # (B, K, hd)
    delta = jax.lax.dot_general(                # (B, hd, hd_v)
        k_out, v, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(l_tot[:, 0])[..., None] * state + delta


def wkv_chunked(r: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, u: jax.Array, *,
                chunk: int = 64, interpret: bool | None = None
                ) -> jax.Array:
    """Chunked WKV over (BH, S, hd) inputs; u (BH, hd).  BH must be a
    multiple of BH_BLOCK and S of ``chunk`` (ops wrapper pads)."""
    bh, s, hd = r.shape
    assert bh % BH_BLOCK == 0 and s % chunk == 0, (bh, s)
    seq_spec = pl.BlockSpec((BH_BLOCK, chunk, hd), lambda i, j: (i, j, 0))

    def call(r, k, v, log_decay, u, *, interpret):
        return pl.pallas_call(
            _wkv_kernel,
            grid=(bh // BH_BLOCK, s // chunk),
            in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                      pl.BlockSpec((BH_BLOCK, hd), lambda i, j: (i, 0))],
            out_specs=seq_spec,
            out_shape=jax.ShapeDtypeStruct((bh, s, hd), r.dtype),
            scratch_shapes=[pltpu.VMEM((BH_BLOCK, hd, hd), jnp.float32)],
            interpret=interpret,
        )(r, k, v, log_decay, u)

    return run_pallas(call, r, k, v, log_decay, u, interpret=interpret)
