"""Pallas TPU kernel for blocked KGE candidate ranking (DESIGN.md §6).

Filtered MRR/Hits@k evaluation scores every test query against up to all N
entity embeddings.  Every registered decoder reduces to the canonical query
form (``repro.models.decoders``):

    ``scores[b, c] = epilogue(q[b]·C'[c] + q_bias[b] + c_bias[c])
                     + filter_bias[b, c]``

which is memory-bound in the candidate stream (arithmetic intensity ≈ d per
candidate row read), so the kernel keeps the query tile resident in VMEM and
streams 128-row candidate tiles from HBM, fusing the rank-1 pre-epilogue
biases, the epilogue and the filtered-setting additive mask into the matmul
(XLA would write the unmasked score matrix to HBM between ops).

Epilogue families (static, selected at trace time):

* ``bilinear`` — identity; DistMult / ComplEx (their ``q_bias``/``c_bias``
  are zero).
* ``neg_l2``   — ``-sqrt(max(x, 0) + NORM_EPS)``: with the norm-expansion
  query (``q = −2u``, ``q_bias = ‖u‖²``, ``c_bias = ‖c‖²``) this is the
  safe negative L2 distance ``−‖u − c‖`` of TransE / RotatE.  The eps sits
  UNDER the sqrt (zero-distance pairs score ``−sqrt(eps)``, gradients stay
  finite) — never inside the difference vector, which would shift every
  score.

The ``filter_bias`` is added AFTER the epilogue: ``-inf`` pad rows and
``FILTER_BIAS`` filtered candidates stay ``-inf``/large-negative on the
score scale for both families, so rank counting over masked scores is exact.
Both epilogues are elementwise and deterministic per (query row, candidate
row), so candidate-axis sharding (``repro.eval.sharded``) reproduces dense
scores bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import run_pallas


Q_BLOCK = 128   # query rows per tile
C_BLOCK = 128   # candidate rows per tile

NORM_EPS = 1e-9          # safe-norm eps, under the sqrt
EPILOGUES = ("bilinear", "neg_l2")


def apply_epilogue(x: jax.Array, epilogue: str) -> jax.Array:
    """The (elementwise, monotone) epilogue families.  Used
    verbatim inside the kernel body and by every XLA-path scorer, so there
    is exactly one definition of the score non-linearity."""
    if epilogue == "bilinear":
        return x
    if epilogue == "neg_l2":
        return -jnp.sqrt(jnp.maximum(x, 0.0) + NORM_EPS)
    raise ValueError(f"unknown epilogue {epilogue!r}; known: {EPILOGUES}")


def _kge_score_kernel(q_ref, cand_ref, qb_ref, cb_ref, bias_ref, out_ref,
                      *, epilogue: str):
    """One (Q_blk, C_blk) tile of
    ``epilogue(q @ cand^T + q_bias + c_bias) + bias``."""
    q = q_ref[...].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, cand_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    scores = scores + qb_ref[...].astype(jnp.float32) \
        + cb_ref[...].astype(jnp.float32)
    scores = apply_epilogue(scores, epilogue)
    out_ref[...] = (scores + bias_ref[...].astype(jnp.float32)).astype(
        out_ref.dtype)


def kge_score(
    q: jax.Array,           # (B, d) prepared query rows
    candidates: jax.Array,  # (C, d) prepared candidate rows
    bias: jax.Array,        # (B, C) POST-epilogue mask (0 / -1e9 / -inf)
    q_bias: jax.Array,      # (B, 1) pre-epilogue per-query bias
    c_bias: jax.Array,      # (1, C) pre-epilogue per-candidate bias
    *, epilogue: str = "bilinear", interpret: bool | None = None,
) -> jax.Array:
    b, d = q.shape
    c = candidates.shape[0]
    assert b % Q_BLOCK == 0 and c % C_BLOCK == 0, \
        "ragged B/C must go through ops.kge_score_padded"
    assert q_bias.shape == (b, 1) and c_bias.shape == (1, c), \
        (q_bias.shape, c_bias.shape)
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}")

    def call(q, candidates, q_bias, c_bias, bias, *, interpret):
        return pl.pallas_call(
            functools.partial(_kge_score_kernel, epilogue=epilogue),
            grid=(b // Q_BLOCK, c // C_BLOCK),
            in_specs=[
                pl.BlockSpec((Q_BLOCK, d), lambda i, j: (i, 0)),
                pl.BlockSpec((C_BLOCK, d), lambda i, j: (j, 0)),
                pl.BlockSpec((Q_BLOCK, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, C_BLOCK), lambda i, j: (0, j)),
                pl.BlockSpec((Q_BLOCK, C_BLOCK), lambda i, j: (i, j)),
            ],
            out_specs=pl.BlockSpec((Q_BLOCK, C_BLOCK), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
            interpret=interpret,
        )(q, candidates, q_bias, c_bias, bias)

    return run_pallas(call, q, candidates, q_bias, c_bias, bias,
                      interpret=interpret)
