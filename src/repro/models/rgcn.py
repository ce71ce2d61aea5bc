"""RGCN encoder (Schlichtkrull et al., 2018) in pure JAX — paper §2.1.

Message passing (paper Eq. 1)::

    h'_s = sigma( W_0 h_s  +  sum_{(r,t) in N_s} (1/c_s) W_r h_t )

with two regularizations from the RGCN paper, both implemented:

* basis decomposition (Eq. 2): ``W_r = sum_b a_rb V_b`` — the configuration
  the paper trains (2 bases on ogbl-citation2);
* block-diagonal decomposition: ``W_r = diag(Q_r1 .. Q_rB)``.

The edge-level compute ``m_e = W_{rel_e} h_{dst_e}`` followed by a segment
sum into ``src_e`` is the hot spot; ``repro.kernels.rgcn_message`` provides
the Pallas TPU kernel, and this module's ``message_passing_ref`` is the pure
jnp implementation used as its oracle and as the CPU path.

Each layer's work carries a ``jax.named_scope`` (HLO ``op_name`` metadata
only; values are untouched): ``kge.message`` for the per-edge messages (the
tail gather, basis projection and coefficient mix, or the Pallas kernel
call whole) and ``kge.aggregate`` for the per-vertex update (the edge mask,
segment sums, degree normalization, self loop, activation, dropout).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class RGCNConfig:
    num_entities: int
    num_relations: int        # AFTER adding inverse relations
    hidden_dim: int = 75      # paper: 75 on FB15k-237, 32 on ogbl-citation2
    num_layers: int = 2       # paper: 2-layer RGCN
    num_bases: int = 2        # paper: 2 basis functions
    feature_dim: Optional[int] = None  # None => learned entity embeddings
    decomposition: str = "basis"       # "basis" | "block" | "none"
    num_blocks: int = 4                # for block-diagonal decomposition
    dropout: float = 0.2
    self_loop: bool = True
    use_kernel: bool = False  # route edge compute through the Pallas kernel
    num_table_shards: int = 1  # >1: entity table stored (S, rows, d), row-
    #   sharded over the model axis (repro.sharding.embedding); the gather
    #   becomes shard-local + exchange, bitwise equal to the dense gather
    gather_exchange: Optional[str] = None  # exchange layout for the sharded
    #   gather (None = per-path default: "fused" sim, "psum_scatter" under
    #   shard_map; see sharding.embedding.SIM_EXCHANGES/SPMD_EXCHANGES) —
    #   all layouts are bitwise equal, this picks the comm pattern only
    table_dtype: str = "fp32"  # "fp32" | "int8": int8 keeps the optimizer's
    #   fp32 MASTER table but runs every gather as quantize → fused-dequant
    #   (repro.sharding.embedding.quantize_rows; int8 codes cross the wire
    #   under shard_map, fp32 per-row scales ride along) — forward values
    #   round to ≤ scale/2 per element, gradients accumulate into the
    #   master bitwise equal to the fp32 path on the dequantized table

    def layer_in_dim(self, layer: int) -> int:
        if layer == 0:
            return self.feature_dim or self.hidden_dim
        return self.hidden_dim


# ====================================================================== #
# Parameters
# ====================================================================== #
def init_rgcn_params(key: jax.Array, cfg: RGCNConfig) -> Dict[str, Any]:
    """Glorot-initialized parameter pytree."""
    params: Dict[str, Any] = {}
    keys = jax.random.split(key, cfg.num_layers * 3 + 1)
    ki = iter(keys)

    if cfg.feature_dim is None:
        table = _glorot(next(ki), (cfg.num_entities, cfg.hidden_dim))
        if cfg.num_table_shards > 1:
            # same values as the dense init (same key), stored row-sharded;
            # padding rows are zero and never gathered, so sharded and
            # replicated models are initialized bitwise identically
            from repro.sharding.embedding import (
                ShardedTableLayout, shard_table,
            )
            table = shard_table(table, ShardedTableLayout(
                cfg.num_entities, cfg.num_table_shards))
        params["entity_embedding"] = table

    layers = []
    for layer in range(cfg.num_layers):
        d_in = cfg.layer_in_dim(layer)
        d_out = cfg.hidden_dim
        lp: Dict[str, Any] = {}
        if cfg.decomposition == "basis":
            lp["bases"] = _glorot(next(ki), (cfg.num_bases, d_in, d_out))
            lp["coeffs"] = _glorot(next(ki), (cfg.num_relations,
                                              cfg.num_bases))
        elif cfg.decomposition == "block":
            if d_in % cfg.num_blocks or d_out % cfg.num_blocks:
                raise ValueError("dims must divide num_blocks")
            lp["blocks"] = _glorot(
                next(ki),
                (cfg.num_relations, cfg.num_blocks,
                 d_in // cfg.num_blocks, d_out // cfg.num_blocks))
        elif cfg.decomposition == "none":
            lp["rel_weight"] = _glorot(
                next(ki), (cfg.num_relations, d_in, d_out))
        else:
            raise ValueError(cfg.decomposition)
        if cfg.self_loop:
            lp["self_weight"] = _glorot(next(ki), (d_in, d_out))
        layers.append(lp)
    params["layers"] = layers
    return params


def _glorot(key: jax.Array, shape) -> jax.Array:
    fan_in, fan_out = shape[-2] if len(shape) > 1 else 1, shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return (jax.random.normal(key, shape) * scale).astype(jnp.float32)


# ====================================================================== #
# Message passing
# ====================================================================== #
def relation_matrices(lp: Dict[str, Any], cfg: RGCNConfig) -> jax.Array:
    """Materialize (R, d_in, d_out) from the decomposition (reference path;
    fine for the R used here, the kernel path never materializes these for
    basis decomposition)."""
    if "bases" in lp:
        return jnp.einsum("rb,bio->rio", lp["coeffs"], lp["bases"])
    if "blocks" in lp:
        r, nb, bi, bo = lp["blocks"].shape
        w = jnp.zeros((r, nb * bi, nb * bo), lp["blocks"].dtype)
        for b in range(nb):
            w = w.at[:, b * bi:(b + 1) * bi, b * bo:(b + 1) * bo].set(
                lp["blocks"][:, b])
        return w
    return lp["rel_weight"]


def message_passing_ref(
    h: jax.Array,            # (V, d_in) vertex states
    src: jax.Array,          # (E,) int32 — edge (s, r, t): message INTO s
    rel: jax.Array,          # (E,) int32
    dst: jax.Array,          # (E,) int32 — message source vertex t
    edge_mask: jax.Array,    # (E,) bool
    lp: Dict[str, Any],
    cfg: RGCNConfig,
) -> jax.Array:
    """Pure-jnp edge compute + mean aggregation: the Pallas oracle.

    Returns (V, d_out) aggregated neighbor messages (NOT including self loop
    / activation — the layer wrapper adds those).
    """
    with jax.named_scope("kge.message"):
        h_t = h[dst]  # (E, d_in) gather tail features
        if "bases" in lp:
            # m_e = sum_b a_[rel_e]b (V_b h_t_e): compute B projections
            # once, then per-edge coefficient mix — O(B·E·d²) ->
            # O(B·V·d² + B·E·d).
            proj = jnp.einsum("ed,bdo->ebo", h_t,
                              lp["bases"])                    # (E, B, d_out)
            coef = lp["coeffs"][rel]                          # (E, B)
            msg = jnp.einsum("ebo,eb->eo", proj, coef)
        elif "blocks" in lp:
            r, nb, bi, bo = lp["blocks"].shape
            e = h_t.shape[0]
            h_blk = h_t.reshape(e, nb, bi)
            w_e = lp["blocks"][rel]                           # (E, nb, bi, bo)
            msg = jnp.einsum("enb,enbo->eno", h_blk, w_e).reshape(e, nb * bo)
        else:
            w_e = lp["rel_weight"][rel]                       # (E, d_in, d_out)
            msg = jnp.einsum("ed,edo->eo", h_t, w_e)

    # the edge mask opens the aggregation: the TPU's scatter fusions keep
    # no metadata of their own and are known by their updates' producer
    with jax.named_scope("kge.aggregate"):
        msg = jnp.where(edge_mask[:, None], msg, 0.0)
        num_v = h.shape[0]
        agg = jax.ops.segment_sum(msg, src, num_segments=num_v)
        deg = jax.ops.segment_sum(edge_mask.astype(h.dtype), src,
                                  num_segments=num_v)
        return agg / jnp.maximum(deg, 1.0)[:, None]


def rgcn_layer(
    h: jax.Array, src: jax.Array, rel: jax.Array, dst: jax.Array,
    edge_mask: jax.Array, lp: Dict[str, Any], cfg: RGCNConfig,
    *, activation=jax.nn.relu, dropout_key: Optional[jax.Array] = None,
) -> jax.Array:
    if cfg.use_kernel and "bases" in lp:
        from repro.kernels.ops import rgcn_message_basis
        with jax.named_scope("kge.message"):
            agg = rgcn_message_basis(
                h, src, rel, dst, edge_mask, lp["bases"], lp["coeffs"])
    else:
        agg = message_passing_ref(h, src, rel, dst, edge_mask, lp, cfg)
    with jax.named_scope("kge.aggregate"):
        if cfg.self_loop:
            agg = agg + h @ lp["self_weight"]
        out = activation(agg)
        if dropout_key is not None and cfg.dropout > 0:
            keep = jax.random.bernoulli(dropout_key, 1 - cfg.dropout,
                                        out.shape)
            out = jnp.where(keep, out / (1 - cfg.dropout), 0.0)
        return out


def rgcn_encode(
    params: Dict[str, Any],
    cfg: RGCNConfig,
    vertex_input: jax.Array,   # (V, F) features OR (V, d) gathered embeddings
    src: jax.Array, rel: jax.Array, dst: jax.Array, edge_mask: jax.Array,
    *, dropout_key: Optional[jax.Array] = None, train: bool = False,
) -> jax.Array:
    """Run all RGCN layers on a (padded) computational graph.

    The final layer keeps a linear output (standard for link prediction —
    scores need signed values).
    """
    h = vertex_input
    n_layers = len(params["layers"])
    keys = (jax.random.split(dropout_key, n_layers)
            if (train and dropout_key is not None) else [None] * n_layers)
    for i, lp in enumerate(params["layers"]):
        act = jax.nn.relu if i < n_layers - 1 else (lambda x: x)
        h = rgcn_layer(h, src, rel, dst, edge_mask, lp, cfg,
                       activation=act, dropout_key=keys[i])
    return h


def count_params(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)))
