"""Offline preprocessing (paper §3.2): partition → expand → pad → budgets.

One function, one artifact: ``preprocess_graph`` turns a training KG into a
``PreprocessedGraph`` holding everything the input pipeline and the SPMD
step need — self-sufficient partitions, the padded full-graph batch, the
replication factor (paper Eq. 7), and (in mini-batch mode) the comp-graph
budgets plus per-partition CSR indices.  The trainer, the launch CLI, the
examples and the benchmarks all go through this seam, so preprocessing can
be cached/sharded later without touching any of them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax.monitoring

from repro.core import (
    BatchBudget, KnowledgeGraph, expand_all, pad_partitions, partition_graph,
    plan_budgets, replication_factor,
)
from repro.core.expansion import PaddedPartitionBatch, SelfSufficientPartition
from repro.core.minibatch import _PartitionCSR
from repro.sharding.embedding import ShardedTableLayout

# prefix of the JAX monitoring events that report set-up stages' seconds
SETUP_EVENT = "/repro/setup/"


@dataclasses.dataclass
class PreprocessedGraph:
    """Everything downstream of offline preprocessing."""

    train_kg: KnowledgeGraph
    partitions: List[SelfSufficientPartition]
    padded: PaddedPartitionBatch
    replication_factor: float
    # mini-batch mode only:
    budget: Optional[BatchBudget] = None
    csrs: Optional[List[_PartitionCSR]] = None
    # entity-table layout when the embedding table is row-sharded over the
    # model axis (repro.sharding.embedding); None = replicated table
    table_layout: Optional[ShardedTableLayout] = None
    # host seconds per stage: partition (with the replication factor),
    # expand, pad, and budgets (mini-batch mode only)
    seconds: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def num_partitions(self) -> int:
        return len(self.partitions)


def preprocess_graph(
    train_kg: KnowledgeGraph,
    *,
    num_trainers: int,
    strategy: str = "vertex_cut",
    num_hops: int = 2,
    seed: int = 0,
    batch_size: Optional[int] = None,
    num_negatives: int = 1,
    sampler: str = "constraint",
    num_table_shards: int = 1,
) -> PreprocessedGraph:
    """Partition ``train_kg`` and make every partition self-sufficient.

    With ``batch_size`` set, also probes the comp-graph budgets (sized
    against the same positive↔negative pairing the mini-batch iterator uses)
    and builds the per-partition in-edge CSRs the hot path gathers from.
    With ``num_table_shards > 1``, derives the entity-table
    ``ShardedTableLayout`` the pipeline's gather plans and the model's
    row-sharded table both follow.

    Each stage's host seconds land in ``seconds`` and are reported as the
    JAX monitoring event ``/repro/setup/<stage>``, which any
    ``jax.monitoring`` duration listener hears.
    """
    seconds: Dict[str, float] = {}
    t = time.perf_counter()

    def stage(name: str) -> None:
        nonlocal t
        now = time.perf_counter()
        seconds[name] = now - t
        jax.monitoring.record_event_duration_secs(
            f"{SETUP_EVENT}{name}", seconds[name])
        t = now

    parts = partition_graph(train_kg, num_trainers, strategy, seed=seed)
    rf = replication_factor(train_kg, parts)
    stage("partition")
    partitions = expand_all(train_kg, parts, num_hops)
    stage("expand")
    pre = PreprocessedGraph(
        train_kg=train_kg,
        partitions=partitions,
        padded=pad_partitions(partitions),
        replication_factor=rf,
        table_layout=(
            ShardedTableLayout(train_kg.num_entities, num_table_shards)
            if num_table_shards > 1 else None),
        seconds=seconds,
    )
    stage("pad")
    if batch_size is not None:
        pre.budget = plan_budgets(
            partitions, batch_size, num_negatives, num_hops, seed=seed,
            sampler=sampler)
        pre.csrs = [_PartitionCSR(p) for p in partitions]
        stage("budgets")
    return pre
