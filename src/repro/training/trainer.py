"""End-to-end distributed KGE training driver — paper Algorithm 1 + §4.

The trainer is a thin composition of four seams, each usable on its own:

* ``repro.training.preprocessing``  — partition → expand → pad → budgets;
* ``repro.data.pipeline``           — serial/async host input pipelines
  (``getComputeGraph`` off the device critical path, double-buffered
  host→device transfer);
* ``repro.training.distributed``    — the SPMD step (vmap simulation on CPU,
  shard_map on real meshes; mathematically identical averaging);
* ``repro.training.evaluation``     — full-graph encoding + filtered ranking.

Timing instrumentation mirrors the paper's Fig. 6 component breakdown:
``t_get_compute_graph`` is the host batch-construction time left on the
critical path (== all of it for the serial pipeline; the exposed remainder
for the async pipeline), ``t_host_build`` the total host construction time,
``overlap_fraction`` how much of it the pipeline hid behind the device step.
``train_epoch`` also names its host work on the profiler's clock (see its
docstring), so a recorded trace says what the host did while the device
idled.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core import KnowledgeGraph
from repro.data.pipeline import (
    FullGraphPipeline, InputPipeline, make_input_pipeline,
)
from repro.models import (
    KGEConfig, RGCNConfig, fullgraph_loss, init_kge_params, minibatch_loss,
)
from repro.training import optimizer as opt_lib
from repro.training.distributed import (
    make_simulated_train_step, split_trainer_keys,
)
from repro.training.evaluation import encode_all_entities, evaluate_split
from repro.training.preprocessing import (
    SETUP_EVENT, PreprocessedGraph, preprocess_graph,
)

# JAX monitoring scalar: the share of the full-graph decoder's rows that
# are real core edges (``PaddedPartitionBatch.core_slot_fill``), recorded
# once at set-up
CORE_SLOT_FILL_EVENT = "/repro/decoder/core_slot_fill"


@dataclasses.dataclass
class TrainConfig:
    num_trainers: int = 4
    strategy: str = "vertex_cut"        # paper's choice; Table 5 ablations
    num_hops: int = 2                   # == RGCN layers
    hidden_dim: int = 32
    num_bases: int = 2
    num_negatives: int = 1
    batch_size: Optional[int] = None    # None => full edge batch (FB15k-237)
    learning_rate: float = 0.01
    dropout: float = 0.2
    epochs: int = 30
    negative_sampler: str = "constraint"  # "constraint" | "global"
    decoder: str = "distmult"
    seed: int = 0
    use_kernel: bool = False
    eval_every: int = 0                 # 0 => only at end
    pipeline: str = "async"             # "async" | "serial" input pipeline
    prefetch: int = 2                   # per-partition prefetch queue depth
    num_table_shards: int = 1           # >1: row-shard the entity embedding
    #   table over the model axis (repro.sharding.embedding); the pipeline
    #   then emits per-shard gather plans with every batch
    sharded_transfer: bool = False      # transfer batches with per-axis
    #   NamedShardings over a host mesh (data×model): each partition slice
    #   lands on its own data-axis device, each gather-plan block on its
    #   model-axis device.  Values are bitwise identical to the
    #   single-device transfer; on a 1-device mesh the paths coincide.
    gather_dedup: bool = False          # dedupe gather plans per trainer
    #   row in the collator: the embedding exchange then moves each unique
    #   id once and expands on device (bitwise-identical output; wins grow
    #   with id skew).  Mini-batch pipelines only — the full-graph resident
    #   batch is transferred once, so there is nothing to save.
    gather_exchange: Optional[str] = None  # sharded-gather exchange layout
    #   (None = per-path default; see sharding.embedding.sharded_gather)
    table_dtype: str = "fp32"           # "fp32" | "int8" entity-table
    #   storage: int8 keeps an fp32 master for the optimizer but every
    #   gather runs quantize → fused-dequant (int8 codes + fp32 per-row
    #   scales cross the wire under shard_map) — values round to ≤ scale/2,
    #   master grads stay bitwise equal to the fp32 path on the
    #   dequantized table (repro.sharding.embedding)
    spmd: Optional[bool] = None         # run the REAL shard_map step over a
    #   data×model mesh (repro.training.distributed.make_spmd_train_step):
    #   params + adam moments placed with kge_param_specs (the row-sharded
    #   entity table stays sharded through the step), batches routed to
    #   per-device placements via BatchShardings on the same mesh.  None =
    #   auto: on when more than one device exists and the mesh fits
    #   (launch.mesh.fit_spmd_mesh — model axis == num_table_shards, data
    #   axis divides num_trainers); True forces it (1×1 mesh allowed);
    #   False keeps the vmap-simulated step.  Both steps are bitwise
    #   identical (tests/test_distributed.py gates losses == and final
    #   params bitwise on a forced 2-device mesh).


class KGETrainer:
    """Owns the preprocessed data, model params, input pipeline and the
    SPMD step."""

    def __init__(self, splits: Dict[str, KnowledgeGraph], cfg: TrainConfig):
        self.cfg = cfg
        self.splits = splits
        train_kg = splits["train"].with_inverse_relations()
        self.train_kg = train_kg

        feat = train_kg.features
        if cfg.num_table_shards > 1 and feat is not None:
            raise ValueError(
                "num_table_shards > 1 requires learned entity embeddings "
                "(feature-mode models have no table to shard)")
        from repro.sharding.embedding import TABLE_DTYPES
        if cfg.table_dtype not in TABLE_DTYPES:
            raise ValueError(
                f"table_dtype={cfg.table_dtype!r} not in {TABLE_DTYPES}")
        if cfg.table_dtype == "int8" and feat is not None:
            raise ValueError(
                "table_dtype='int8' requires learned entity embeddings "
                "(feature-mode models have no table to quantize)")

        # ---- offline preprocessing (paper §3.2) ----
        self.pre: PreprocessedGraph = preprocess_graph(
            train_kg,
            num_trainers=cfg.num_trainers, strategy=cfg.strategy,
            num_hops=cfg.num_hops, seed=cfg.seed,
            batch_size=cfg.batch_size, num_negatives=cfg.num_negatives,
            sampler=cfg.negative_sampler,
            num_table_shards=cfg.num_table_shards,
        )

        # ---- model ----
        self.kge_cfg = KGEConfig(
            rgcn=RGCNConfig(
                num_entities=train_kg.num_entities,
                num_relations=train_kg.num_relations,
                hidden_dim=cfg.hidden_dim,
                num_layers=cfg.num_hops,
                num_bases=cfg.num_bases,
                feature_dim=None if feat is None else feat.shape[1],
                dropout=cfg.dropout,
                use_kernel=cfg.use_kernel,
                num_table_shards=cfg.num_table_shards,
                gather_exchange=cfg.gather_exchange,
                table_dtype=cfg.table_dtype,
            ),
            decoder=cfg.decoder,
            num_negatives=cfg.num_negatives,
            negative_sampler=cfg.negative_sampler,
        )
        t0 = time.perf_counter()
        key = jax.random.PRNGKey(cfg.seed)
        self.params = init_kge_params(key, self.kge_cfg)
        self.features = None if feat is None else jnp.asarray(feat)

        optimizer = opt_lib.adam(cfg.learning_rate)
        self.optimizer = optimizer
        self.opt_state = optimizer.init(self.params)
        jax.monitoring.record_event_duration_secs(
            f"{SETUP_EVENT}init", time.perf_counter() - t0)
        self._key = jax.random.PRNGKey(cfg.seed + 1)
        self._epoch = 0
        self._steps = 0     # global step count: the trace's step numbers

        # ---- mesh + step selection (simulated vmap vs real shard_map) ----
        self._fullgraph = cfg.batch_size is None
        self.mesh = None
        self._spmd = self._resolve_spmd()
        self._model_axis = "model" if self._spmd else None
        self._validate_exchange()

        if self._spmd:
            from repro.launch.mesh import fit_spmd_mesh, make_host_mesh
            self.mesh = make_host_mesh(*fit_spmd_mesh(
                cfg.num_trainers, cfg.num_table_shards))
            self._place_state()

        # ---- input pipeline ----
        if self._spmd:
            # spmd batches always transfer with mesh-aware placements:
            # partition slices over the data axis, gather-plan blocks over
            # the model axis — the step's in_specs, so no resharding
            from repro.data.pipeline import BatchShardings
            shardings = BatchShardings(self.mesh)
        elif cfg.sharded_transfer:
            shardings = self._make_batch_shardings()
        else:
            shardings = None
        # full-graph: the resident batch is reused every epoch, so its
        # buffers must NOT be donated (and there is nothing to dedup).
        # mini-batch: streamed batches die after their step — donate their
        # buffers to the exchange (no-op with a warning on CPU, so gate it)
        donate = not self._fullgraph and jax.default_backend() != "cpu"
        loss = self._fullgraph_loss if self._fullgraph \
            else self._minibatch_loss
        if self._spmd:
            from repro.training.distributed import make_spmd_train_step
            self.step = make_spmd_train_step(
                loss, optimizer, self.mesh,
                param_specs=self._param_specs,
                model_axis="model", donate_batch=donate)
        else:
            self.step = make_simulated_train_step(
                loss, optimizer, donate_batch=donate)
        if self._fullgraph:
            jax.monitoring.record_scalar(
                CORE_SLOT_FILL_EVENT, self.pre.padded.core_slot_fill())
            self.pipeline: InputPipeline = FullGraphPipeline(
                self.pre.padded, table_layout=self.pre.table_layout,
                shardings=shardings)
        else:
            self.pipeline = make_input_pipeline(
                cfg.pipeline, self.pre.partitions,
                batch_size=cfg.batch_size,
                num_negatives=cfg.num_negatives,
                num_hops=cfg.num_hops,
                budget=self.pre.budget,
                seed=cfg.seed,
                sampler=cfg.negative_sampler,
                csrs=self.pre.csrs,
                prefetch=cfg.prefetch,
                table_layout=self.pre.table_layout,
                shardings=shardings,
                dedup_gather=cfg.gather_dedup,
            )

    def _resolve_spmd(self) -> bool:
        """``cfg.spmd`` tri-state: explicit True/False wins (True validates
        the mesh fits and raises otherwise); None auto-enables the real
        shard_map step exactly when it buys parallelism — more than one
        local device AND the mesh fits (``fit_spmd_mesh``)."""
        from repro.launch.mesh import fit_spmd_mesh
        cfg = self.cfg
        fit = fit_spmd_mesh(cfg.num_trainers, cfg.num_table_shards)
        if cfg.spmd is None:
            return fit is not None and fit[0] * fit[1] > 1
        if cfg.spmd and fit is None:
            raise ValueError(
                f"spmd=True needs {cfg.num_table_shards} model-axis "
                f"devices for {cfg.num_table_shards} table shards but "
                f"only {jax.device_count()} devices exist")
        return bool(cfg.spmd)

    def _validate_exchange(self) -> None:
        """Fail fast on an exchange layout the selected step can't run:
        the vmap simulation implements ``SIM_EXCHANGES``, the shard_map
        step the collective ``SPMD_EXCHANGES`` — ``None`` always resolves
        to the right per-path default."""
        from repro.sharding.embedding import SIM_EXCHANGES, SPMD_EXCHANGES
        ex = self.cfg.gather_exchange
        allowed = SPMD_EXCHANGES if self._spmd else SIM_EXCHANGES
        if ex is not None and ex not in allowed:
            kind = "spmd" if self._spmd else "simulated"
            raise ValueError(
                f"gather_exchange={ex!r} is not available on the {kind} "
                f"step (one of {allowed}); leave it None for the default")

    def _place_state(self) -> None:
        """Place params and optimizer state on the mesh BEFORE the first
        step: the row-sharded entity table (and its adam moments) start —
        and stay — distributed with ``kge_param_specs`` instead of being
        resharded out of a replicated copy on the first dispatch."""
        from repro.sharding import kge_param_specs, tree_named_shardings
        from repro.training.distributed import derive_opt_state_specs
        self._param_specs = kge_param_specs(self.params, self.mesh)
        self._opt_specs = derive_opt_state_specs(
            self.opt_state, self.params, self._param_specs)
        self.params = jax.device_put(
            self.params, tree_named_shardings(self._param_specs, self.mesh))
        self.opt_state = jax.device_put(
            self.opt_state, tree_named_shardings(self._opt_specs, self.mesh))

    def _make_batch_shardings(self):
        """Mesh-aware transfer placements for ``cfg.sharded_transfer``: the
        data×model host mesh using the MOST local devices such that the
        ``data`` axis divides the trainer count and the ``model`` axis the
        table shard count (ties prefer the data axis — trainer slices
        dominate transfer bytes; 1×1, the bitwise-identical degenerate
        case, when only one device exists)."""
        from repro.data.pipeline import BatchShardings
        from repro.launch.mesh import make_host_mesh
        cfg = self.cfg
        ndev = jax.device_count()
        data, model = max(
            ((d, m) for d in range(1, ndev + 1)
             if cfg.num_trainers % d == 0
             for m in range(1, ndev // d + 1)
             if cfg.num_table_shards % m == 0),
            key=lambda dm: (dm[0] * dm[1], dm[0]))
        return BatchShardings(make_host_mesh(data, model))

    # ------------------------------------------------------------------ #
    # preprocessing artifacts (stable public surface)
    # ------------------------------------------------------------------ #
    @property
    def partitions(self):
        return self.pre.partitions

    @property
    def padded(self):
        return self.pre.padded

    @property
    def replication_factor(self) -> float:
        return self.pre.replication_factor

    @property
    def budget(self):
        return self.pre.budget

    # ------------------------------------------------------------------ #
    def _fullgraph_loss(self, params, batch, key):
        return fullgraph_loss(params, self.kge_cfg, batch, key,
                              features=self.features, train=True,
                              model_axis=self._model_axis)

    def _minibatch_loss(self, params, batch, key):
        return minibatch_loss(params, self.kge_cfg, batch,
                              features=self.features, dropout_key=key,
                              model_axis=self._model_axis)

    # ------------------------------------------------------------------ #
    def next_step_args(self, batch=None) -> tuple:
        """``(params, opt_state, batch, keys)`` of the next epoch's first
        step, exactly as ``train_epoch`` passes them to ``step``.
        ``batch`` defaults to the pipeline's first batch of that epoch.
        A reference run replays these arguments on another device."""
        if batch is None:
            it = self.pipeline.device_batches(self._epoch + 1)
            batch = next(iter(it))
            close = getattr(it, "close", None)
            if close is not None:
                close()
        keys = split_trainer_keys(self._key, self.cfg.num_trainers,
                                  self._epoch + 1)
        if not self._fullgraph:
            keys = jax.vmap(jax.random.fold_in, (0, None))(keys, 0)
        return self.params, self.opt_state, batch, keys

    def lower_step(self, batch=None):
        """``jax.stages.Lowered`` of the trainer's jitted train step for
        one real pipeline batch (:meth:`next_step_args`) — the entry point
        the SPMD contract auditor (``repro.analysis.programs``) lowers
        each production configuration through; compile the result and
        read ``.as_text()`` for the post-optimization per-device
        module."""
        return self.step.lower(*self.next_step_args(batch))

    # ------------------------------------------------------------------ #
    def train_epoch(self) -> Dict[str, float]:
        """One epoch; its host work opens ``jax.profiler`` spans (about a
        microsecond each when no trace is recording): ``train.epoch``
        around it, ``train.keys`` around the key split, a
        ``pipeline.next_batch`` before each step (the last finds the epoch
        ended and opens no step), and per step ``train.step`` (a
        ``StepTraceAnnotation`` numbered by the global step) holding
        ``train.keys`` (the mini-batch fold-in), ``train.dispatch`` (the
        jitted step's call) and ``train.wait`` (until its loss is on the
        host)."""
        cfg = self.cfg
        self._epoch += 1
        t_device = 0.0
        losses = []
        with TraceAnnotation("train.epoch"):
            with TraceAnnotation("train.keys"):
                keys = split_trainer_keys(self._key, cfg.num_trainers,
                                          self._epoch)
            nbatches = 0
            batches = iter(self.pipeline.device_batches(self._epoch))
            while True:
                with TraceAnnotation("pipeline.next_batch"):
                    batch = next(batches, None)
                if batch is None:
                    break
                with StepTraceAnnotation("train.step",
                                         step_num=self._steps):
                    if self._fullgraph:
                        skeys = keys  # one update per epoch; keys fresh
                    else:
                        with TraceAnnotation("train.keys"):
                            skeys = jax.vmap(jax.random.fold_in,
                                             (0, None))(keys, nbatches)
                    t0 = time.perf_counter()
                    with TraceAnnotation("train.dispatch"):
                        self.params, self.opt_state, m = self.step(
                            self.params, self.opt_state, batch, skeys)
                    with TraceAnnotation("train.wait"):
                        jax.block_until_ready(m["loss"])
                        t_device += time.perf_counter() - t0
                        losses.append(float(m["loss"]))
                nbatches += 1
                self._steps += 1

            stats = self.pipeline.last_stats
            return {
                "epoch": self._epoch,
                "loss": float(np.mean(losses)) if losses else float("nan"),
                "t_get_compute_graph": stats.exposed_wait_s,
                "t_host_build": stats.host_build_s,
                "t_warmup": stats.warmup_s,
                "overlap_fraction": stats.overlap_fraction(),
                "t_device_step": t_device,
                "t_epoch": stats.warmup_s + stats.exposed_wait_s + t_device,
                "num_batches": nbatches,
            }

    def fit(self, epochs: Optional[int] = None,
            log_fn=None) -> List[Dict[str, float]]:
        history = []
        for _ in range(epochs or self.cfg.epochs):
            rec = self.train_epoch()
            if self.cfg.eval_every and \
                    self._epoch % self.cfg.eval_every == 0:
                rec.update(self.evaluate("valid"))
            history.append(rec)
            if log_fn:
                log_fn(rec)
        return history

    def close(self) -> None:
        self.pipeline.close()

    # ------------------------------------------------------------------ #
    # checkpointing: params + optimizer state + the TRAINER-side state
    # (epoch counter, PRNG key) that the per-epoch key schedule
    # (``split_trainer_keys(key, P, epoch)``) depends on — without both, a
    # resumed run silently restarts the negative-sampling / dropout RNG
    # stream at epoch 1 and diverges from the uninterrupted run.
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, directory: str, keep: int = 3) -> str:
        """One checkpoint per call, stamped with the current epoch; the
        manifest ``metadata`` carries ``epoch`` and the raw PRNG ``key``
        so ``restore`` continues the exact RNG stream."""
        from repro.training.checkpoint import save_checkpoint
        tree = {"params": self.params, "opt": self.opt_state}
        meta = {
            "epoch": int(self._epoch),
            "key": np.asarray(self._key, dtype=np.uint32).tolist(),
        }
        return save_checkpoint(directory, self._epoch, tree,
                               metadata=meta, keep=keep)

    def restore(self, path: str) -> int:
        """Resume from ``save_checkpoint`` output: restores params +
        optimizer state (entity tables convert across storage layouts and
        shard counts — checkpoints are layout-portable), then the epoch
        counter and PRNG key from the manifest metadata, so the next
        ``train_epoch`` draws the SAME keys the uninterrupted run would
        have.  Under spmd the restored (host) arrays are re-placed on the
        mesh.  Returns the restored epoch."""
        from repro.training.checkpoint import read_metadata, \
            restore_checkpoint
        like = {"params": self.params, "opt": self.opt_state}
        step, tree = restore_checkpoint(
            path, like, entity_rows=self.train_kg.num_entities)
        self.params, self.opt_state = tree["params"], tree["opt"]
        _, meta = read_metadata(path)
        self._epoch = int(meta.get("epoch", step))
        if "key" in meta:
            self._key = jnp.asarray(np.asarray(meta["key"],
                                               dtype=np.uint32))
        if self._spmd:
            self._place_state()
        return self._epoch

    # ------------------------------------------------------------------ #
    def encode_all_entities(self) -> np.ndarray:
        """Evaluation-time encoder pass: stream ``encode_partition`` over
        the TRAINING partitions (reusing ``self.pre`` — no re-partitioning)
        and scatter each partition's core vertices into the global matrix."""
        return encode_all_entities(
            self.params, self.kge_cfg, self.train_kg, self.cfg.num_hops,
            features=self.features, partitions=self.pre.partitions,
            padded=self.pre.padded)

    def evaluate(self, split: str = "test") -> Dict[str, float]:
        """Filtered MRR / Hits@k through the scaled eval subsystem: streamed
        partition encoding + (with ``num_table_shards > 1``) candidate-axis-
        sharded ranking over the row-sharded entity table."""
        return evaluate_split(
            self.params, self.kge_cfg, self.splits, split,
            self.cfg.num_hops, self.cfg.decoder, features=self.features,
            partitions=self.pre.partitions, padded=self.pre.padded)
