"""KGE serving driver: the sharded top-k engine under a request stream.

Stands up a :class:`repro.serving.ShardedKGEServer` (synthetic entity table
+ decoder params, or a table trained in-process with ``--train-epochs``),
wraps it in the dynamic-batching :class:`repro.serving.KGEServeEngine`, and
drives a Zipf-skewed query stream through it — printing p50/p99 request
latency and QPS, plus the sharded == dense top-k equality check the
subsystem is contracted on (``docs/serving.md``).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --table-shards 4 --topk 10
  PYTHONPATH=src python -m repro.launch.serve --decoder rotate \
      --filtered --cache-size 256 --requests 200
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def build_server(args):
    from repro.core.graph import KnowledgeGraph
    from repro.eval.ranking import CSRFilterIndex
    from repro.models.decoders import init_decoder_params
    from repro.serving import ShardedKGEServer

    rng = np.random.default_rng(args.seed)
    emb = rng.normal(scale=0.1, size=(args.entities, args.dim)
                     ).astype(np.float32)
    params = init_decoder_params(jax.random.PRNGKey(args.seed),
                                 args.decoder, args.relations, args.dim)
    filter_index = None
    if args.filtered:
        e = max(args.entities * 4, 64)   # synthetic known-triplet store
        g = KnowledgeGraph(src=rng.integers(0, args.entities, e),
                           rel=rng.integers(0, args.relations, e),
                           dst=rng.integers(0, args.entities, e),
                           num_entities=args.entities,
                           num_relations=args.relations)
        filter_index = CSRFilterIndex.build([g])
    server = ShardedKGEServer(
        emb, params, args.decoder, num_shards=args.table_shards,
        filter_index=filter_index, cache_size=args.cache_size,
        table_dtype=args.table_dtype)
    return server, emb, params


def check_equal_dense(server, emb, params, args) -> bool:
    """The serving contract: sharded top-k == dense ``jax.lax.top_k``
    (over the dequantized table for ``--table-dtype int8`` — dequant is
    an exact pow2 multiply, so equality stays exact)."""
    from repro.models.decoders import score_against_candidates

    if args.table_dtype == "int8":
        from repro.sharding.embedding import dequantize_rows, quantize_rows
        emb = np.asarray(dequantize_rows(*quantize_rows(emb)))
    rng = np.random.default_rng(args.seed + 1)
    heads = rng.integers(0, args.entities, args.slots)
    rels = rng.integers(0, args.relations, args.slots)
    k = min(args.topk, args.entities)
    dense = score_against_candidates(
        params, args.decoder, jnp.asarray(emb[heads]),
        jnp.asarray(rels.astype(np.int32)), jnp.asarray(emb))
    _, want = jax.lax.top_k(dense, k)
    _, got = server.topk_tails(heads, rels, k)
    return bool((got == np.asarray(want)).all())


def main() -> None:
    from repro.models.decoders import registered_decoders

    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=5000)
    ap.add_argument("--relations", type=int, default=16)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--decoder", default="distmult",
                    choices=registered_decoders())
    ap.add_argument("--table-shards", type=int, default=1,
                    help="row-shard the entity table over this many "
                         "candidate-axis shards (the (B, N) score matrix "
                         "is never materialized for any value)")
    ap.add_argument("--topk", type=int, default=10,
                    help="engine-wide max k (per-request k is clamped to "
                         "it; one jitted step shape)")
    ap.add_argument("--slots", type=int, default=8,
                    help="dynamic-batching width — requests per step")
    ap.add_argument("--policy", default="fifo",
                    choices=("fifo", "smallest-k-first"),
                    help="admission policy (smallest-k-first decouples "
                         "completion from submission order)")
    ap.add_argument("--filtered", action="store_true",
                    help="filter known tails via the column-range "
                         "CSRFilterIndex bias (serving sentinel t=-1)")
    ap.add_argument("--table-dtype", default="fp32",
                    choices=("fp32", "int8"),
                    help="entity-table storage: int8 keeps only row-wise "
                         "symmetric codes + fp32 pow2 scales on device "
                         "(~0.27x bytes at d=64) and fuses the dequant "
                         "into the top-k program")
    ap.add_argument("--cache-size", type=int, default=0,
                    help="hot-entity head-embedding LRU entries "
                         "(0 disables; bits never change)")
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--zipf", type=float, default=1.3,
                    help="head-entity skew of the query stream (serving "
                         "traffic is hot-entity heavy; drives the cache)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import KGEServeEngine

    enable_compile_cache()

    server, emb, params = build_server(args)
    engine = KGEServeEngine(server, slots=args.slots, max_k=args.topk,
                            filtered=args.filtered, policy=args.policy)
    print(f"[serve] {args.decoder} over {args.entities} entities, "
          f"{args.table_shards}-shard table "
          f"(rows/shard={server.layout.rows_per_shard}), "
          f"slots={args.slots}, max_k={engine.max_k}"
          + (", int8 table" if args.table_dtype == "int8" else "")
          + (", filtered" if args.filtered else "")
          + (f", cache={args.cache_size}" if args.cache_size else ""))

    rng = np.random.default_rng(args.seed + 2)
    heads = np.minimum(rng.zipf(args.zipf, args.requests) - 1,
                       args.entities - 1)
    rels = rng.integers(0, args.relations, args.requests)

    # warmup: compile the fixed-shape step once
    engine.submit(int(heads[0]), int(rels[0]), k=engine.max_k)
    engine.run()

    lat = []
    t_start = time.perf_counter()
    for lo in range(0, args.requests, args.slots):
        for i in range(lo, min(lo + args.slots, args.requests)):
            engine.submit(int(heads[i]), int(rels[i]), k=engine.max_k)
        t0 = time.perf_counter()
        done = engine.run()
        dt = time.perf_counter() - t0
        lat.extend([dt] * len(done))     # batch-synchronous latency
    wall = time.perf_counter() - t_start
    lat_ms = np.sort(np.array(lat) * 1e3)
    p50 = float(np.percentile(lat_ms, 50))
    p99 = float(np.percentile(lat_ms, 99))
    print(f"[serve] {args.requests} requests in {wall:.2f}s — "
          f"{args.requests / wall:.1f} QPS, "
          f"p50={p50:.2f}ms p99={p99:.2f}ms")
    if args.cache_size:
        tot = server.cache_hits + server.cache_misses
        print(f"[serve] head cache: {server.cache_hits}/{tot} hits "
              f"({server.cache_hits / max(tot, 1):.0%})")
    ok = check_equal_dense(server, emb, params, args)
    print(f"[serve] sharded top-k == dense jax.lax.top_k: {ok}")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
