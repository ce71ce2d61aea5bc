"""Pallas TPU kernels for the RGCN hot spot (DESIGN.md §6).

GPU frameworks implement relational message passing as per-edge
gather→GEMM→atomic-scatter.  TPUs have no atomic scatter and favor large
MXU matmuls from VMEM, so the op is re-thought as two tiled kernels:

1. ``basis_message`` — per-edge basis projection + coefficient mix, edges
   tiled in MXU-aligned blocks of 128.  Fuses the ``B`` basis projections
   with the coefficient mix in VMEM, never materializing the (E, B, d_out)
   intermediate that the XLA einsum path writes to HBM.

2. ``segment_sum_onehot`` — scatter-free segment sum: for an output vertex
   tile and an edge tile, build the 0/1 incidence tile
   ``onehot[v, e] = (src_e == v)`` with iota-compare and accumulate
   ``onehot @ msg`` on the MXU.  This trades FLOPs (V_blk per edge) for the
   systolic array's throughput — the standard TPU substitute for atomic
   scatter.
   Edges pre-sorted by head vertex make the incidence tile block-diagonal so
   most (i, j) grid cells see an all-zero tile; a cheap in-kernel range test
   skips their compute (``pl.when``).

Both kernels are compiled when lowered for TPU and interpreted elsewhere
(``repro.kernels.platform``).  Oracles: ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import run_pallas


EDGE_BLOCK = 128     # MXU-aligned edge tile
VERTEX_BLOCK = 128   # output vertex tile


# ====================================================================== #
# Kernel 1: basis message
# ====================================================================== #
def _basis_message_kernel(h_t_ref, coef_ref, mask_ref, bases_ref, out_ref):
    """One edge tile: out = mask * sum_b coef[:, b] * (h_t @ bases[b]).

    Block shapes:
      h_t_ref  (E_blk, d_in)   coef_ref (E_blk, B)   mask_ref (E_blk, 1)
      bases_ref (B, d_in, d_out)  — replicated to every tile (fits VMEM:
      B·d_in·d_out ≤ 2·256·256·4B = 512 KiB at our sizes)
      out_ref  (E_blk, d_out)
    """
    h_t = h_t_ref[...]
    num_bases = bases_ref.shape[0]
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for b in range(num_bases):  # static unroll: B is small (paper uses 2)
        proj = jax.lax.dot_general(
            h_t, bases_ref[b],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + coef_ref[:, b][:, None].astype(jnp.float32) * proj
    out_ref[...] = (acc * mask_ref[...].astype(jnp.float32)).astype(
        out_ref.dtype)


def basis_message(
    h_t: jax.Array,       # (E, d_in)
    coef: jax.Array,      # (E, B)
    bases: jax.Array,     # (B, d_in, d_out)
    edge_mask: jax.Array,  # (E,)
    *, interpret: bool | None = None,
) -> jax.Array:
    """Tiled fused basis message computation.  E must be a multiple of
    EDGE_BLOCK (the ops wrapper pads)."""
    e, d_in = h_t.shape
    num_bases, _, d_out = bases.shape
    assert e % EDGE_BLOCK == 0, "pad edges to EDGE_BLOCK"
    mask2d = edge_mask.astype(jnp.float32)[:, None]

    def call(h_t, coef, mask2d, bases, *, interpret):
        return pl.pallas_call(
            _basis_message_kernel,
            grid=(e // EDGE_BLOCK,),
            in_specs=[
                pl.BlockSpec((EDGE_BLOCK, d_in), lambda j: (j, 0)),
                pl.BlockSpec((EDGE_BLOCK, num_bases), lambda j: (j, 0)),
                pl.BlockSpec((EDGE_BLOCK, 1), lambda j: (j, 0)),
                pl.BlockSpec((num_bases, d_in, d_out),
                             lambda j: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((EDGE_BLOCK, d_out), lambda j: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((e, d_out), h_t.dtype),
            interpret=interpret,
        )(h_t, coef, mask2d, bases)

    return run_pallas(call, h_t, coef, mask2d, bases, interpret=interpret)


# ====================================================================== #
# Kernel 2: one-hot segment sum (+ degree counts)
# ====================================================================== #
def _segment_sum_kernel(msg_ref, seg_ref, mask_ref, out_ref, deg_ref,
                        *, num_v_blocks: int):
    """Grid (i over vertex tiles, j over edge tiles); j is the minor
    (fastest) dimension so each output tile accumulates across all edge
    tiles before the grid moves to the next vertex tile."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        deg_ref[...] = jnp.zeros_like(deg_ref)

    seg = seg_ref[...][:, 0]                      # (E_blk,)
    mask = mask_ref[...][:, 0]                    # (E_blk,)
    v_lo = i * VERTEX_BLOCK
    local = seg - v_lo                            # (E_blk,)
    # Skip tiles whose edges can't touch this vertex tile (edges sorted by
    # head make hits block-diagonal; unsorted inputs just skip the skip).
    hit = jnp.any((local >= 0) & (local < VERTEX_BLOCK) & (mask > 0))

    @pl.when(hit)
    def _accum():
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (VERTEX_BLOCK, local.shape[0]), 0)
        onehot = jnp.where(
            (rows == local[None, :]) & (mask[None, :] > 0), 1.0, 0.0
        ).astype(jnp.float32)                      # (V_blk, E_blk)
        out_ref[...] += jax.lax.dot_general(
            onehot, msg_ref[...].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)
        deg_ref[...] += jnp.sum(
            onehot, axis=1, keepdims=True).astype(deg_ref.dtype)


def segment_sum_onehot(
    msg: jax.Array,       # (E, d)
    seg: jax.Array,       # (E,) int32
    edge_mask: jax.Array,  # (E,)
    num_segments: int,
    *, interpret: bool | None = None,
):
    """Masked segment sum via MXU one-hot matmuls.
    Returns (agg (V, d), deg (V, 1)).  V padded to VERTEX_BLOCK by wrapper."""
    e, d = msg.shape
    assert e % EDGE_BLOCK == 0 and num_segments % VERTEX_BLOCK == 0
    nv = num_segments // VERTEX_BLOCK
    ne = e // EDGE_BLOCK
    seg2d = seg.astype(jnp.int32)[:, None]
    mask2d = edge_mask.astype(jnp.int32)[:, None]
    kernel = functools.partial(_segment_sum_kernel, num_v_blocks=nv)

    def call(msg, seg2d, mask2d, *, interpret):
        return pl.pallas_call(
            kernel,
            grid=(nv, ne),
            in_specs=[
                pl.BlockSpec((EDGE_BLOCK, d), lambda i, j: (j, 0)),
                pl.BlockSpec((EDGE_BLOCK, 1), lambda i, j: (j, 0)),
                pl.BlockSpec((EDGE_BLOCK, 1), lambda i, j: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((VERTEX_BLOCK, d), lambda i, j: (i, 0)),
                pl.BlockSpec((VERTEX_BLOCK, 1), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((num_segments, d), msg.dtype),
                jax.ShapeDtypeStruct((num_segments, 1), msg.dtype),
            ],
            interpret=interpret,
        )(msg, seg2d, mask2d)

    agg, deg = run_pallas(call, msg, seg2d, mask2d, interpret=interpret)
    return agg, deg
