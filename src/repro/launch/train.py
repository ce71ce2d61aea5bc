"""Training driver.

Two families behind one CLI (the framework's two model families share the
distributed runtime — DESIGN.md §4):

* ``--arch rgcn-fb15k237`` / ``rgcn-citation2`` — the paper's distributed
  KGE training (partition → expand → edge mini-batch → AllReduce), at a
  ``--scale`` that fits the local machine; real FB15k-237 files are used
  when ``--data-root`` points at them.
* ``--arch <assigned-arch>`` — reduced-config LM training on the synthetic
  token stream (exercises the same train_step the dry-run lowers at
  production scale).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch rgcn-fb15k237 \
      --trainers 4 --epochs 20
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --steps 50
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def train_kge(args) -> None:
    from repro.data import load_or_synthesize
    from repro.training import KGETrainer
    from repro.configs import RGCN_FB15K237, RGCN_CITATION2

    name = "fb15k-237" if args.arch == "rgcn-fb15k237" else "ogbl-citation2"
    base = RGCN_FB15K237 if name == "fb15k-237" else RGCN_CITATION2
    splits = load_or_synthesize(name, data_root=args.data_root,
                                scale=args.scale)
    cfg = dataclasses.replace(
        base, num_trainers=args.trainers, epochs=args.epochs,
        batch_size=args.batch_size if args.batch_size > 0 else
        (None if name == "fb15k-237" else 4096),
        strategy=args.strategy, use_kernel=args.use_kernel,
        pipeline=args.pipeline, prefetch=args.prefetch,
        num_table_shards=args.table_shards,
        sharded_transfer=args.sharded_transfer,
        gather_dedup=args.gather_dedup,
        gather_exchange=args.gather_exchange,
        table_dtype=args.table_dtype,
        spmd=args.spmd,
        decoder=args.decoder, num_negatives=args.num_negatives,
        **({"hidden_dim": args.hidden_dim} if args.hidden_dim > 0 else {}))
    pipe = ("full-graph (resident batch)" if cfg.batch_size is None
            else f"{cfg.pipeline} pipeline")   # --pipeline/--prefetch only
    #                                            drive the mini-batch path
    xfer = ", sharded transfer" if cfg.sharded_transfer else ""
    xfer += ", deduped gather" if cfg.gather_dedup else ""
    if cfg.gather_exchange:
        xfer += f", {cfg.gather_exchange} exchange"
    if cfg.table_dtype != "fp32":
        xfer += f", {cfg.table_dtype} table"
    print(f"[train] {name}: {splits['train'].num_edges} train edges, "
          f"{splits['train'].num_entities} entities; "
          f"{cfg.decoder} decoder, {cfg.num_negatives} negatives/edge; "
          f"{cfg.num_trainers} trainers ({cfg.strategy}, {pipe}{xfer}, "
          f"{cfg.num_table_shards}-shard entity table)")
    trainer = KGETrainer(splits, cfg)
    if trainer.mesh is not None:
        print(f"[train] spmd shard_map step on a "
              f"{dict(trainer.mesh.shape)} mesh "
              f"({jax.device_count()} local devices)")
    else:
        print(f"[train] simulated (vmap) step"
              + (" — --spmd forced off" if cfg.spmd is False else
                 f" — mesh does not fit {jax.device_count()} device(s)"))
    print(f"[train] RF={trainer.replication_factor:.2f}")
    trainer.fit(log_fn=lambda r: print(
        f"  epoch {r['epoch']:3d} loss={r['loss']:.4f} "
        f"t={r['t_epoch']:.2f}s (host exposed "
        f"{r['t_get_compute_graph']:.2f}s of {r['t_host_build']:.2f}s, "
        f"overlap {r['overlap_fraction']:.0%})"))
    # eval reuses the training partitions (streamed encoder) and, with
    # --table-shards > 1, ranks candidate-axis-sharded over the row blocks
    t0 = time.perf_counter()
    metrics = trainer.evaluate("test")
    rank_mode = (f"{cfg.num_table_shards}-shard ranking"
                 if cfg.num_table_shards > 1 else "dense ranking")
    print(f"[eval] {cfg.decoder} decoder, {rank_mode}, "
          f"{len(trainer.partitions)}-partition "
          f"streamed encode, {time.perf_counter() - t0:.2f}s")
    print("[eval]", metrics)


def train_lm(args) -> None:
    from repro.configs import get_arch
    from repro.data import TokenStream
    from repro.launch.steps import make_train_step
    from repro.nn import init_params
    from repro.training.optimizer import adam

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    optimizer = adam(args.lr)
    opt_state = optimizer.init(params)
    step = jax.jit(make_train_step(cfg, optimizer), donate_argnums=(0, 1))
    stream = TokenStream(cfg.vocab_size, args.batch, args.seq)
    print(f"[train] {cfg.name}: "
          f"{sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)):,.0f} params")
    it = iter(stream)
    for i in range(args.steps):
        raw = next(it)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.arch_type == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, args.seq, cfg.vision_dim), jnp.float32)
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, :, None],
                (args.batch, args.seq, 3)).astype(jnp.int32)
        if cfg.arch_type == "encdec":
            batch["audio_frames"] = jnp.zeros(
                (args.batch, cfg.encoder_frames, cfg.d_model), jnp.float32)
        t0 = time.perf_counter()
        params, opt_state, metrics = step(params, opt_state, batch)
        loss = float(metrics["loss"])
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f"  step {i:4d} loss={loss:.4f} "
                  f"({time.perf_counter() - t0:.2f}s)")
    assert np.isfinite(loss), "training diverged"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--trainers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=-1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--strategy", default="vertex_cut")
    ap.add_argument("--pipeline", default="async",
                    choices=("async", "serial"),
                    help="host input pipeline for mini-batch training")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="per-partition prefetch queue depth")
    ap.add_argument("--table-shards", type=int, default=1,
                    help="row-shard the entity embedding table over this "
                         "many model-axis shards (1 = replicated)")
    ap.add_argument("--sharded-transfer", action="store_true",
                    help="transfer batches with per-axis NamedShardings "
                         "over a data x model host mesh (each partition "
                         "slice to its own data-axis device, gather-plan "
                         "blocks to model-axis devices); bitwise identical "
                         "to the single-device transfer")
    ap.add_argument("--gather-dedup", action="store_true",
                    help="dedupe sharded-gather plans per trainer row in "
                         "the collator (exchange each unique id once, "
                         "expand on device; bitwise-identical output)")
    ap.add_argument("--spmd", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="run the REAL shard_map train step over a "
                         "data x model device mesh (params + adam moments "
                         "placed with kge_param_specs; the row-sharded "
                         "entity table stays distributed through the "
                         "step).  Default: auto — on when >1 device "
                         "exists and the mesh fits (model axis == "
                         "--table-shards, data axis divides --trainers); "
                         "--no-spmd keeps the vmap-simulated step.  Both "
                         "are bitwise identical")
    ap.add_argument("--gather-exchange", default=None,
                    choices=("fused", "masked_sum", "psum", "psum_scatter",
                             "alltoall"),
                    help="sharded-gather exchange layout (default: fused "
                         "on the sim path, psum_scatter under shard_map; "
                         "all layouts are bitwise equal)")
    ap.add_argument("--table-dtype", default="fp32",
                    choices=("fp32", "int8"),
                    help="entity-table storage: int8 stores row-wise "
                         "symmetric codes + fp32 per-row scales "
                         "(~0.27x the fp32 bytes at d=64) with dequant "
                         "fused into the gather; the optimizer keeps an "
                         "fp32 master, so training dynamics match the "
                         "fp32 path on the dequantized table")
    from repro.models.decoders import registered_decoders
    ap.add_argument("--decoder", default="distmult",
                    choices=registered_decoders(),
                    help="KGE scoring function (registry-resolved; the "
                         "paper trains distmult)")
    ap.add_argument("--num-negatives", type=int, default=1,
                    help="negative samples per positive edge (paper: 1)")
    ap.add_argument("--hidden-dim", type=int, default=-1,
                    help="override the arch config's hidden dim (complex/"
                         "rotate need an even dim; fb15k-237's paper dim "
                         "is 75)")
    ap.add_argument("--data-root", default=None)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--reduced", action="store_true", default=True)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.arch.startswith("rgcn-"):
        train_kge(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
