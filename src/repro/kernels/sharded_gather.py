"""Pallas TPU kernels for the sharded-embedding gather hot path.

The PR-2 sharded entity table made per-device memory scale 1/S but left the
gather 3-4x SLOWER than the dense gather it replaced: the shard-local
take → mask → sum/psum chain materializes an (S, V, d) intermediate and
touches ``S × V × d`` elements where the dense gather touches ``V × d``
(``BENCH_embedding.json``, ROADMAP open item 2).  Exactly one shard owns
every id, so the mask+accumulate is pure bookkeeping — it can be folded
into the *index arithmetic*:

    ``flat[v] = Σ_s owned[s, v] ? s · rows + local_ids[s, v] : 0``

turns the per-shard plan back into one flat row index into the stacked
``(S · rows, d)`` table, and the whole chain collapses to a single masked
row gather — the exchange's masked sum never exists as data movement.
This module provides that collapsed op as Pallas kernels:

* ``fused_gather`` — forward: one output row per grid step; the row index
  is a scalar-prefetch argument (``PrefetchScalarGridSpec``), so the block
  index map DMAs exactly the owner's row from the stacked table, and the
  ownership mask, carried in the index's sign, is applied in-register.
  No (S, V, d) intermediate, no S-way elementwise mask, no reduction.
* ``fused_dequant_gather`` — the int8 variant: same grid, but the DMA'd
  row is an int8 code row plus its (1, 1) fp32 per-row scale, and the
  dequantize (``codes.astype(f32) · scale``) happens in-register — the
  fp32 table is never materialized (``repro.sharding.embedding``'s
  quantized layout).
* ``scatter_add_onehot`` — backward: the transpose scatter-add as tiled
  one-hot matmuls (the TPU substitute for atomic scatter, same pattern as
  ``rgcn_message.segment_sum_onehot``): for a (row tile, cotangent tile)
  pair build the 0/1 incidence tile and accumulate ``onehot @ g`` on the
  MXU, skipping tiles no cotangent row hits.

Each is compiled when lowered for TPU and interpreted elsewhere
(``repro.kernels.platform``).
Oracles: ``repro.kernels.ref.sharded_gather_ref`` (the original
take→mask→sum chain) and ``ref.sharded_scatter_add_ref``.  The jit-ready
entry point with the custom VJP (and the XLA lowering used on non-TPU
backends, bit-equal by construction) is ``repro.kernels.ops.
fused_sharded_gather``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import run_pallas


ROW_BLOCK = 128   # table-row tile of the scatter-add kernel
COT_BLOCK = 128   # cotangent-row tile of the scatter-add kernel


# ====================================================================== #
# Forward: fused gather + mask (+ the accumulate folded into flat ids)
# ====================================================================== #
# One gathered row per grid step.  The TPU lowering tiles the last two
# block dimensions by (8, 128) unless they span the whole array, so a
# single-row block of an (R, d) table is refused.  The kernels therefore
# view the table as (R, 1, d) and take a squeezed (None, 1, d) block: the
# last two dimensions are then whole.  Ownership rides in the sign of the
# scalar-prefetched row index (-1 = no shard owns the slot), so a row no
# shard owns is zeroed in-register and its DMA reads row 0.
def _signed_rows(flat_ids: jax.Array, any_owned: jax.Array) -> jax.Array:
    return jnp.where(any_owned, flat_ids.astype(jnp.int32), -1)


def _row_block(width: int) -> pl.BlockSpec:
    """The owner's row of an (R, 1, width) operand, by prefetched index."""
    return pl.BlockSpec((None, 1, width),
                        lambda i, rows: (jnp.maximum(rows[i], 0), 0, 0))


def _out_block(d: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, 1, d), lambda i, rows: (i, 0, 0))


def _fused_gather_kernel(rows_ref, table_ref, out_ref):
    """``table_ref`` is the (1, d) row the prefetched index selected; a
    slot no shard owns (dedup-plan padding) is zeroed in-register — the
    fused remnant of the old exchange mask."""
    owned = rows_ref[pl.program_id(0)] >= 0
    out_ref[...] = jnp.where(owned, table_ref[...], 0.0)


def fused_gather(
    table_flat: jax.Array,  # (R, d) stacked table, R = S * rows_per_shard
    flat_ids: jax.Array,    # (V,) int32 flat row index (owner-resolved)
    any_owned: jax.Array,   # (V,) bool/int — does ANY shard own this slot
    *, interpret: bool | None = None,
) -> jax.Array:
    """Fused gather+mask: ``out[v] = any_owned[v] ? table_flat[flat_ids[v]]
    : 0`` — the collapsed form of the shard-local take → mask → sum chain
    (``ref.sharded_gather_ref``), one row DMA per output row."""
    v = flat_ids.shape[0]
    r, d = table_flat.shape

    def call(rows, table, *, interpret):
        return pl.pallas_call(
            _fused_gather_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(v,),
                in_specs=[_row_block(d)], out_specs=_out_block(d)),
            out_shape=jax.ShapeDtypeStruct((v, 1, d), table.dtype),
            interpret=interpret,
        )(rows, table)

    out = run_pallas(call, _signed_rows(flat_ids, any_owned),
                     table_flat.reshape(r, 1, d), interpret=interpret)
    return out.reshape(v, d)


# ====================================================================== #
# Forward (int8): fused dequantize + gather + mask
# ====================================================================== #
def _fused_dequant_gather_kernel(rows_ref, codes_ref, scale_ref, out_ref):
    """int8 twin of ``_fused_gather_kernel``: the prefetched index DMAs
    the owner's (1, d) int8 code row AND its (1, 1) fp32 scale; the row
    is dequantized in-register (``codes.astype(f32) · scale``) — the fp32
    row never exists outside this tile."""
    owned = rows_ref[pl.program_id(0)] >= 0
    row = codes_ref[...].astype(jnp.float32) * scale_ref[...]
    out_ref[...] = jnp.where(owned, row, 0.0)


def fused_dequant_gather(
    codes_flat: jax.Array,   # (R, d) int8 stacked row codes
    scales_flat: jax.Array,  # (R,) fp32 per-row scales
    flat_ids: jax.Array,     # (V,) int32 flat row index (owner-resolved)
    any_owned: jax.Array,    # (V,) bool/int — does ANY shard own this slot
    *, interpret: bool | None = None,
) -> jax.Array:
    """Fused dequantizing gather: ``out[v] = any_owned[v] ?
    codes_flat[flat_ids[v]].astype(f32) · scales_flat[flat_ids[v]] : 0``.
    Same grid/DMA structure as :func:`fused_gather` with one extra (1, 1)
    scale operand riding the same index map; output is fp32.  Oracle:
    ``ref.dequant_gather_ref``."""
    v = flat_ids.shape[0]
    r, d = codes_flat.shape

    def call(rows, codes, scales, *, interpret):
        return pl.pallas_call(
            _fused_dequant_gather_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(v,),
                in_specs=[_row_block(d), _row_block(1)],
                out_specs=_out_block(d)),
            out_shape=jax.ShapeDtypeStruct((v, 1, d), jnp.float32),
            interpret=interpret,
        )(rows, codes, scales)

    out = run_pallas(call, _signed_rows(flat_ids, any_owned),
                     codes_flat.reshape(r, 1, d),
                     scales_flat.astype(jnp.float32).reshape(r, 1, 1),
                     interpret=interpret)
    return out.reshape(v, d)


# ====================================================================== #
# Backward: fused scatter-add of the gather cotangents
# ====================================================================== #
def _scatter_add_kernel(flat_ref, g_ref, mask_ref, out_ref):
    """Grid (i over table-row tiles, j over cotangent tiles); j is the
    minor (fastest) dimension so each row tile accumulates across all
    cotangent tiles before the grid moves on (same accumulation contract
    as ``rgcn_message._segment_sum_kernel``)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    flat = flat_ref[...][:, 0]                     # (COT_BLOCK,)
    mask = mask_ref[...][:, 0]                     # (COT_BLOCK,)
    local = flat - pl.program_id(0) * ROW_BLOCK
    hit = jnp.any((local >= 0) & (local < ROW_BLOCK) & (mask > 0))

    @pl.when(hit)
    def _accum():
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (ROW_BLOCK, local.shape[0]), 0)
        onehot = jnp.where(
            (rows == local[None, :]) & (mask[None, :] > 0), 1.0, 0.0
        ).astype(jnp.float32)                      # (ROW_BLOCK, COT_BLOCK)
        out_ref[...] += jax.lax.dot_general(
            onehot, g_ref[...].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)


def scatter_add_onehot(
    g: jax.Array,          # (V, d) gather cotangents
    flat_ids: jax.Array,   # (V,) int32 flat destination rows
    any_owned: jax.Array,  # (V,) bool/int — unowned slots contribute 0
    num_rows: int,         # R = S * rows_per_shard (padded table rows)
    *, interpret: bool | None = None,
) -> jax.Array:
    """Scatter-free transpose of :func:`fused_gather`:
    ``out[r] = Σ_v (flat_ids[v] == r ∧ any_owned[v]) · g[v]`` via MXU
    one-hot matmuls.  V and ``num_rows`` must be tile multiples (the ops
    wrapper pads; padded cotangent rows carry ``any_owned=False``)."""
    v, d = g.shape
    assert v % COT_BLOCK == 0 and num_rows % ROW_BLOCK == 0, \
        "pad V/num_rows to tile multiples (ops wrapper)"
    def call(flat, g, owned, *, interpret):
        return pl.pallas_call(
            _scatter_add_kernel,
            grid=(num_rows // ROW_BLOCK, v // COT_BLOCK),
            in_specs=[
                pl.BlockSpec((COT_BLOCK, 1), lambda i, j: (j, 0)),
                pl.BlockSpec((COT_BLOCK, d), lambda i, j: (j, 0)),
                pl.BlockSpec((COT_BLOCK, 1), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((ROW_BLOCK, d), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((num_rows, d), jnp.float32),
            interpret=interpret,
        )(flat, g, owned)

    return run_pallas(call, flat_ids.astype(jnp.int32)[:, None], g,
                      any_owned.astype(jnp.int32)[:, None],
                      interpret=interpret)
