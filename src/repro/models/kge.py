"""Full GNN-based KGE model: RGCN encoder + decoder (paper Fig. 1).

Two execution shapes:

* ``minibatch_loss`` — edge mini-batch (Algorithm 1): comp-graph arrays from
  ``repro.core.minibatch``, gather vertex inputs from the global table, run
  RGCN, score the batch triplets, BCE loss.
* ``fullgraph_loss`` — full-edge-batch training on a padded partition (the
  paper's FB15k-237 setting) with device-side constraint-based negatives.

Both are jit/shard_map friendly (fixed shapes, no host callbacks).

The layers carry ``jax.named_scope`` names, which reach the compiled
program's HLO ``op_name`` metadata (forward, ``transpose(jvp(...))``
backward and rematerialized copies alike) and change no value:
``kge.gather`` (vertex inputs, and under ``jax.grad`` their scatter into
the table gradient), ``kge.message`` and ``kge.aggregate``
(``repro.models.rgcn``), ``kge.decoder_loss`` (negatives, scores, loss)
and ``kge.optimizer`` (``repro.training.distributed``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.negative import (
    constraint_based_negatives, global_closed_world_negatives, mix_pos_neg,
)
from repro.models import decoders
from repro.models.rgcn import RGCNConfig, init_rgcn_params, rgcn_encode
from repro.sharding.embedding import (
    plan_local_gather_device, sharded_gather,
)


@dataclasses.dataclass(frozen=True)
class KGEConfig:
    rgcn: RGCNConfig
    # registry name or Decoder instance (paper Eq. 4 default); resolved
    # ONLY through repro.models.decoders.get_decoder
    decoder: Union[str, decoders.Decoder] = "distmult"
    num_negatives: int = 1      # paper: 1 on ogbl-citation2
    negative_sampler: str = "constraint"   # "constraint" | "global"

    @property
    def decoder_impl(self) -> decoders.Decoder:
        return decoders.get_decoder(self.decoder)

    @property
    def num_entities(self) -> int:
        return self.rgcn.num_entities

    @property
    def num_table_shards(self) -> int:
        return self.rgcn.num_table_shards


def init_kge_params(key: jax.Array, cfg: KGEConfig) -> Dict[str, Any]:
    k_enc, k_dec = jax.random.split(key)
    params = init_rgcn_params(k_enc, cfg.rgcn)
    params["decoder"] = decoders.init_decoder_params(
        k_dec, cfg.decoder, cfg.rgcn.num_relations, cfg.rgcn.hidden_dim)
    return params


def vertex_input(params: Dict[str, Any], cfg: KGEConfig,
                 gather_global: jax.Array,
                 features: Optional[jax.Array],
                 shard_local_ids: Optional[jax.Array] = None,
                 shard_owned: Optional[jax.Array] = None,
                 shard_inverse: Optional[jax.Array] = None,
                 *, model_axis: Optional[str] = None) -> jax.Array:
    """Gather the per-vertex model input: learned embedding rows
    (transductive) or precomputed features (ogbl-citation2 style).

    With a row-sharded entity table (``(S, rows, d)``, see
    ``repro.sharding.embedding``) the dense gather becomes a shard-local
    gather + exchange, driven by a host-precomputed ``ShardedGatherPlan``
    (``shard_local_ids`` / ``shard_owned``, emitted by the input pipeline)
    or, when none is provided (full-graph / evaluation paths), by the
    identical in-jit plan.  A deduped plan additionally carries
    ``shard_inverse`` — the plan covers each id once and the inverse map
    expands the exchanged rows back to batch slots on device.
    ``model_axis`` names the mesh axis when running inside ``shard_map``;
    ``None`` selects the single-device simulation; ``cfg.rgcn.
    gather_exchange`` picks the exchange layout — every combination is
    bitwise equal to the replicated dense gather.
    """
    if cfg.rgcn.feature_dim is None:
        table = params["entity_embedding"]
        table_dtype = cfg.rgcn.table_dtype
        if table.ndim == 2 and table_dtype == "int8":
            # dense (unsharded) master: run the same quantized gather over
            # a single-shard stack so the int8 semantics don't depend on
            # num_table_shards
            table = table[None]
        if table.ndim == 3:
            if shard_local_ids is None:
                num_shards = (table.shape[0] if model_axis is None
                              else jax.lax.psum(1, model_axis))
                shard_local_ids, shard_owned = plan_local_gather_device(
                    num_shards, table.shape[1], gather_global)
            return sharded_gather(table, shard_local_ids, shard_owned,
                                  axis_name=model_axis,
                                  exchange=cfg.rgcn.gather_exchange,
                                  inverse=shard_inverse,
                                  table_dtype=table_dtype)
        return table[gather_global]
    assert features is not None, "feature-mode model needs features"
    return features[gather_global]


# ====================================================================== #
# Edge mini-batch loss (Algorithm 1 inner loop)
# ====================================================================== #
def minibatch_loss(
    params: Dict[str, Any],
    cfg: KGEConfig,
    batch: Dict[str, jax.Array],
    features: Optional[jax.Array] = None,
    dropout_key: Optional[jax.Array] = None,
    model_axis: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Loss on one padded EdgeMiniBatch (fields as device arrays; batches
    from a sharded-table pipeline also carry the precomputed gather plan
    under ``shard_local_ids`` / ``shard_owned``)."""
    with jax.named_scope("kge.gather"):
        x = vertex_input(params, cfg, batch["gather_global"], features,
                         batch.get("shard_local_ids"),
                         batch.get("shard_owned"),
                         batch.get("shard_inverse"), model_axis=model_axis)
        x = jnp.where(batch["vertex_mask"][:, None], x, 0.0)
    h = rgcn_encode(
        params, cfg.rgcn, x,
        batch["comp_src"], batch["comp_rel"], batch["comp_dst"],
        batch["comp_mask"], dropout_key=dropout_key,
        train=dropout_key is not None)
    with jax.named_scope("kge.decoder_loss"):
        scores = decoders.score_triplets(
            params["decoder"], cfg.decoder, h, batch["triplets"])
        mask = batch["triplet_mask"].astype(jnp.float32)
        loss = decoders.bce_loss(scores, batch["labels"], mask)
        pos = batch["labels"] > 0.5
        aux = {
            "loss": loss,
            "pos_score_mean": jnp.sum(scores * mask * pos) /
            jnp.maximum(jnp.sum(mask * pos), 1.0),
            "neg_score_mean": jnp.sum(scores * mask * (1 - pos)) /
            jnp.maximum(jnp.sum(mask * (1 - pos)), 1.0),
        }
    return loss, aux


# ====================================================================== #
# Full-graph loss on a padded self-sufficient partition
# ====================================================================== #
def fullgraph_loss(
    params: Dict[str, Any],
    cfg: KGEConfig,
    part: Dict[str, jax.Array],   # one slice of PaddedPartitionBatch
    rng: jax.Array,
    features: Optional[jax.Array] = None,
    train: bool = True,
    model_axis: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-edge-batch training step on one padded partition (paper's
    FB15k-237 configuration).  Negatives are sampled ON DEVICE from the
    partition's core vertices — legal because the full partition graph is the
    computational graph, so every core vertex already has an embedding.
    Every local edge carries messages; only the core edges, listed by
    ``core_edge_index``, are scored."""
    k_neg, k_drop = jax.random.split(rng)
    with jax.named_scope("kge.gather"):
        x = vertex_input(params, cfg, part["local_to_global"], features,
                         part.get("shard_local_ids"),
                         part.get("shard_owned"),
                         part.get("shard_inverse"), model_axis=model_axis)
        x = jnp.where(part["vertex_mask"][:, None], x, 0.0)
    h = rgcn_encode(
        params, cfg.rgcn, x,
        part["src"], part["rel"], part["dst"], part["edge_mask"],
        dropout_key=k_drop if train else None, train=train)

    with jax.named_scope("kge.decoder_loss"):
        pos = jnp.stack([part["src"], part["rel"], part["dst"]], axis=1)
        # negatives are drawn for every local edge, so each edge keeps the
        # corruptions its key gives it whatever rows are scored below
        if cfg.negative_sampler == "global":
            # baseline ablation: corrupt with ANY local vertex (the closest
            # analogue of the closed-world sampler inside one partition's
            # address space — a true global draw would need remote fetches)
            neg, _ = global_closed_world_negatives(
                k_neg, pos, cfg.num_negatives,
                int(part["local_to_global"].shape[0]))
        else:
            neg, _ = constraint_based_negatives(
                k_neg, pos, cfg.num_negatives, part["num_core_vertices"])
        # score only the core edges (support edges carry messages, not
        # loss): their positives and their own s negatives, edge-major
        idx = part["core_edge_index"]
        c, s = idx.shape[0], cfg.num_negatives
        neg = neg.reshape(pos.shape[0], s, 3)[idx].reshape(c * s, 3)
        trip, labels = mix_pos_neg(pos[idx], neg)
        core = (part["core_edge_mask"][idx]
                & (jnp.arange(c) < part["num_core_edges"]))
        core = core.astype(jnp.float32)
        mask = jnp.concatenate([core, jnp.repeat(core, s)], axis=0)

        scores = decoders.score_triplets(params["decoder"], cfg.decoder, h,
                                         trip)
        loss = decoders.bce_loss(scores, labels, mask)
    return loss, {"loss": loss}


# ====================================================================== #
# Encoding for evaluation (embeds every local vertex of a partition)
# ====================================================================== #
def encode_partition(
    params: Dict[str, Any], cfg: KGEConfig, part: Dict[str, jax.Array],
    features: Optional[jax.Array] = None,
) -> jax.Array:
    with jax.named_scope("kge.gather"):
        x = vertex_input(params, cfg, part["local_to_global"], features,
                         part.get("shard_local_ids"),
                         part.get("shard_owned"), part.get("shard_inverse"))
        x = jnp.where(part["vertex_mask"][:, None], x, 0.0)
    return rgcn_encode(
        params, cfg.rgcn, x,
        part["src"], part["rel"], part["dst"], part["edge_mask"])
