"""Pallas TPU kernels for the paper's compute hot spots:

* ``rgcn_message`` — fused basis-decomposed relational message passing
  (gather → basis projection+mix → MXU one-hot segment sum).
* ``kge_score`` — blocked candidate ranking in the canonical decoder query
  form ``epilogue(q @ C'^T + q_bias + c_bias) + mask`` — one kernel carries
  every registered decoder (``repro.models.decoders``).
* ``sharded_gather`` — fused flat-index gather / one-hot scatter-add for
  the row-sharded entity table exchange.
* ``topk`` — deterministic per-shard top-k selection (serving: reduce each
  shard's score block to ``(B, k)`` so the dense ``(B, N)`` matrix never
  materializes; ties break toward the lowest index, matching
  ``jax.lax.top_k``).
* ``wkv_chunk`` — chunked RWKV-6 WKV with VMEM-resident recurrent state
  (the §Perf-winning formulation, TPU-native).

``ops`` holds the jit'd public wrappers; ``ref`` the pure-jnp oracles.
Each kernel is compiled when a program is lowered for TPU and interpreted
on every other platform (``platform``); ``tests/test_tpu_compile.py``
compiles the main-path kernels for a described v5e chip at FB15k-237
widths.
"""
from repro.kernels import ops, ref
from repro.kernels.kge_score import EPILOGUES, NORM_EPS, apply_epilogue
from repro.kernels.ops import (
    kge_score_padded, merge_topk, rgcn_message_basis, topk_padded,
    wkv_chunked_op,
)

__all__ = ["ops", "ref", "EPILOGUES", "NORM_EPS", "apply_epilogue",
           "kge_score_padded", "merge_topk", "rgcn_message_basis",
           "topk_padded", "wkv_chunked_op"]
