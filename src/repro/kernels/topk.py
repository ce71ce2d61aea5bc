"""Pallas TPU kernel for per-shard streaming top-k selection.

The serving engine (``repro.serving``) answers ``(head, relation, ?)``
queries with the k best tails.  The dense path materializes the full
``(B, N)`` score matrix on one device and runs ``jax.lax.top_k`` — the
memory wall the candidate-axis-sharded table was built to remove.  The
sharded path instead scores each shard's ``(B, rows/S)`` block with the
``kge_score`` kernel, reduces it to ``(B, k)`` *immediately* with this
kernel, and k-way-merges the per-shard winners — the ``(B, N)`` matrix
never exists on any device (peak score memory per device is one
``(B, rows/S)`` block, and only ``S · B · k`` merge candidates cross
shards).

Selection contract (the serving ``==``-vs-dense gate depends on it):

    k iterations of  (max over still-active columns,
                      LOWEST column index among the maxima wins,
                      winner deactivated)

which is exactly ``jax.lax.top_k``'s documented order — values descending,
ties broken toward the lower index — so per-shard top-k + merge reproduces
the dense ``jax.lax.top_k`` indices EXACTLY (shard row blocks are
contiguous global-id ranges: among equal values, a lower global id is an
earlier shard or a lower local index, both of which the merge preserves).
The oracle is ``kernels.ref.topk_ref`` (same algorithm, pure jnp);
``tests/test_serving.py`` asserts kernel == ref == ``jax.lax.top_k``.

The ``active`` mask — not a ``-inf`` substitution — is what keeps ties
exact: a selected ``-inf`` score (layout-padded rows, filtered
candidates) would be re-selected forever if masking rewrote values, but
deactivation removes the *column*, so repeated ``-inf`` entries drain in
ascending index order exactly like ``lax.top_k``.

The grid is (query tiles, candidate tiles).  The candidate axis is cut
into ``TOPK_C_BLOCK``-column tiles so VMEM holds one ``(Q_BLOCK,
TOPK_C_BLOCK)`` score tile whatever C is (a whole ``(128, 14592)`` row
block with its masks does not fit the TPU's scoped VMEM).  The ``(Q, k)``
outputs stay resident across the candidate tiles and carry the running
top-k of the columns seen so far; each tile runs the same k selection
rounds over the running list and the tile together.  A running entry
always sits at a lower column than any entry of the current tile, so on
equal values the running entry wins — the same lowest-index order.
Running slots not yet filled hold index ``-1`` and are never active; the
selection masks are int32, because the TPU lowering cannot carry a
``bool`` array through the loop.  The jit-ready wrapper with B/C padding,
k-clamping and the dispatch to the bit-identical ``jax.lax.top_k`` off
TPU is ``repro.kernels.ops.topk_padded``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import run_pallas


TOPK_Q_BLOCK = 128    # query rows per grid step
TOPK_C_BLOCK = 2048   # candidate columns per grid step (VMEM bound)
_NO_COL = jnp.iinfo(jnp.int32).max


def _topk_kernel(scores_ref, vals_ref, idx_ref, *, k: int, num_cols: int):
    """Merge one (Q, c_block) score tile into the running (Q, k) top-k."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)

    scores = scores_ref[...].astype(jnp.float32)          # (Q, c_block)
    q, cb = scores.shape
    col = j * cb + jax.lax.broadcasted_iota(jnp.int32, (q, cb), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)
    run_v = vals_ref[...]
    run_i = idx_ref[...]

    def body(r, carry):
        tile_on, run_on, vals, idx = carry
        tile_cur = jnp.where(tile_on != 0, scores, -jnp.inf)
        run_cur = jnp.where(run_on != 0, run_v, -jnp.inf)
        m = jnp.maximum(jnp.max(tile_cur, axis=1),
                        jnp.max(run_cur, axis=1))          # (Q,)
        # the winner: lowest ACTIVE position attaining the max, running
        # list first ("active" matters — when m == -inf every inactive
        # entry also compares equal)
        run_pos = jnp.min(jnp.where(
            (run_on != 0) & (run_cur == m[:, None]), kcol, k), axis=1)
        tile_col = jnp.min(jnp.where(
            (tile_on != 0) & (tile_cur == m[:, None]), col, _NO_COL), axis=1)
        from_run = run_pos < k
        run_col = jnp.max(jnp.where(kcol == run_pos[:, None], run_i, -1),
                          axis=1)
        pick = jnp.where(from_run, run_col,
                         jnp.where(tile_col < _NO_COL, tile_col, -1))
        vals = jnp.where(kcol == r, m[:, None], vals)
        idx = jnp.where(kcol == r, pick[:, None], idx)
        run_on = jnp.where(kcol == run_pos[:, None], 0, run_on)
        tile_on = jnp.where(
            from_run[:, None] | (col != tile_col[:, None]), tile_on, 0)
        return tile_on, run_on, vals, idx

    _, _, vals, idx = jax.lax.fori_loop(
        0, k, body,
        ((col < num_cols).astype(jnp.int32), (run_i >= 0).astype(jnp.int32),
         jnp.full((q, k), -jnp.inf, jnp.float32),
         jnp.full((q, k), -1, jnp.int32)))
    vals_ref[...] = vals
    idx_ref[...] = idx


def topk_scores(
    scores: jax.Array,      # (B, C) float score block
    k: int,
    *, num_cols: int | None = None,
    c_block: int = TOPK_C_BLOCK,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k per row of a score block: ``(values (B, k), indices (B, k))``,
    values descending, ties broken toward the LOWEST index — bit-equal to
    ``jax.lax.top_k`` on float32 scores.  B must be a ``TOPK_Q_BLOCK``
    multiple and C a ``c_block`` multiple; only the first ``num_cols``
    columns (default all) are candidates, and ``k <= num_cols``
    (``ops.topk_padded`` pads/clamps ragged callers)."""
    b, c = scores.shape
    num_cols = c if num_cols is None else num_cols
    assert b % TOPK_Q_BLOCK == 0 and c % c_block == 0, \
        "ragged B/C must go through ops.topk_padded"
    assert 1 <= k <= num_cols <= c, \
        f"k={k} outside [1, C={num_cols}] — ops.topk_padded clamps"

    def call(s, *, interpret):
        return pl.pallas_call(
            functools.partial(_topk_kernel, k=k, num_cols=num_cols),
            grid=(b // TOPK_Q_BLOCK, c // c_block),
            in_specs=[pl.BlockSpec((TOPK_Q_BLOCK, c_block),
                                   lambda i, j: (i, j))],
            out_specs=[
                pl.BlockSpec((TOPK_Q_BLOCK, k), lambda i, j: (i, 0)),
                pl.BlockSpec((TOPK_Q_BLOCK, k), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, k), jnp.float32),
                jax.ShapeDtypeStruct((b, k), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(s)

    vals, idx = run_pallas(call, scores, interpret=interpret)
    return vals, idx
