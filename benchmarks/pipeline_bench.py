"""Input-pipeline benchmark: serial vs async epoch wall-clock and the
host/device overlap fraction (the Fig. 6 bottleneck, attacked), plus the
sharded-entity-table variant: per-step gather+exchange time and the
embedding-table bytes each device has to hold at 1/2/4/8 model shards
(the memory wall row-sharding removes).

Writes ``BENCH_pipeline.json`` and ``BENCH_embedding.json`` next to the
repo root so both perf trajectories are recorded across PRs, and emits the
usual CSV rows via ``benchmarks.run``.

Run: PYTHONPATH=src python -m benchmarks.pipeline_bench [--full]
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

from benchmarks.common import emit
import numpy as np

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_pipeline.json")
EMBED_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                               "BENCH_embedding.json")


def _hbm_per_device(tr) -> int:
    """Max bytes any one device holds for params + optimizer state (the
    spmd row's capacity headline: row-sharding the entity table divides
    its — and its adam moments' — footprint across the model axis)."""
    import jax
    per: Dict[int, int] = {}
    for arr in jax.tree_util.tree_leaves((tr.params, tr.opt_state)):
        if not hasattr(arr, "addressable_shards"):
            continue
        for sh in arr.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return max(per.values()) if per else 0


def _measure(splits, kind: str, quick: bool,
             sharded_transfer: bool = False,
             spmd=None) -> Dict[str, float]:
    from repro.training import KGETrainer, TrainConfig

    tr = KGETrainer(splits, TrainConfig(
        num_trainers=4, strategy="vertex_cut", num_hops=2, hidden_dim=32,
        num_negatives=1, batch_size=256, learning_rate=0.01, seed=0,
        pipeline=kind, sharded_transfer=sharded_transfer, spmd=spmd))
    tr.train_epoch()                      # warmup + compile epoch
    epochs = 2 if quick else 5
    walls, recs = [], []
    for _ in range(epochs):
        t0 = time.perf_counter()
        rec = tr.train_epoch()
        walls.append(time.perf_counter() - t0)
        recs.append(rec)
    return {
        "epoch_wall_s": float(np.median(walls)),
        "host_build_s": float(np.median(
            [r["t_host_build"] for r in recs])),
        "host_exposed_s": float(np.median(
            [r["t_get_compute_graph"] for r in recs])),
        "device_step_s": float(np.median(
            [r["t_device_step"] for r in recs])),
        "overlap_fraction": float(np.median(
            [r["overlap_fraction"] for r in recs])),
        "num_batches": int(recs[0]["num_batches"]),
        "hbm_per_device_bytes": _hbm_per_device(tr),
    }


AUDIT_DEVICES = 4      # forced host devices for the comm audit (2x2 mesh)


def _comm_audit(quick: bool) -> Dict:
    """Run the SPMD contract auditor (``repro.launch.audit``) in a
    subprocess — it needs a forced multi-device CPU platform, and this
    process's jax already locked the real device count — and return its
    ``comm_audit`` rows for the pipeline payload.  Quick mode audits one
    exchange layout (both dedup settings) + rank + serve; full mode
    every layout."""
    import subprocess
    import sys
    import tempfile

    cmd = [sys.executable, "-m", "repro.launch.audit",
           "--devices", str(AUDIT_DEVICES), "--quiet"]
    if quick:
        cmd += ["--exchanges", "psum_scatter"]
    with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
        cmd += ["--json", tmp.name]
        env = dict(os.environ)
        # the audit lowers on a forced CPU mesh by design; without this
        # the child would reach for the accelerator this process holds
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             env.get("PYTHONPATH", "")])
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              env=env, timeout=1200)
        if proc.returncode != 0:
            # keep the violation table in the payload — the run.py gate
            # raises on it, with the table in the error message
            return {"ok": False, "returncode": proc.returncode,
                    "table": proc.stdout, "stderr": proc.stderr[-2000:],
                    "rows": []}
        with open(tmp.name) as f:
            rows = json.load(f)["comm_audit"]
    return {"ok": all(r["ok"] for r in rows), "returncode": 0,
            "table": proc.stdout, "rows": rows}


def run(quick: bool = True) -> List[Dict]:
    from repro.data import synthetic_citation2

    splits = synthetic_citation2(scale=0.0008 if quick else 0.002, seed=0)
    kg = splits["train"]
    results = {kind: _measure(splits, kind, quick)
               for kind in ("serial", "async")}
    # per-axis NamedSharding device_put instead of jnp.asarray (on a
    # 1-device box this measures the pure placement-API overhead; on a
    # real mesh it buys the per-device slice placement)
    results["async_sharded"] = _measure(splits, "async", quick,
                                        sharded_transfer=True)
    # the REAL shard_map step (spmd=True forces it even on the 1-device
    # box, where the 1x1 mesh measures pure shard_map dispatch overhead
    # vs the vmap simulation; on a multi-device host it runs the mesh
    # fit_spmd_mesh picks) — step time + per-device param/opt-state HBM
    import jax
    results["spmd"] = _measure(splits, "async", quick, spmd=True)
    speedup = results["serial"]["epoch_wall_s"] / \
        max(results["async"]["epoch_wall_s"], 1e-9)

    payload = {
        "bench": "pipeline",
        "graph": {"entities": int(kg.num_entities),
                  "edges": int(kg.num_edges)},
        "config": {"trainers": 4, "batch_size": 256, "num_hops": 2,
                   "hidden_dim": 32, "quick": quick,
                   "devices": int(jax.device_count())},
        "serial": results["serial"],
        "async": results["async"],
        "async_sharded_transfer": results["async_sharded"],
        "spmd": results["spmd"],
        "async_speedup": round(speedup, 3),
        # static SPMD contract audit: collective whitelist + closed-form
        # byte budget per production program (repro.analysis)
        "comm_audit": _comm_audit(quick),
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    rows = []
    for kind in ("serial", "async", "async_sharded", "spmd"):
        r = results[kind]
        rows.append({
            "name": kind,
            "us_per_call": r["epoch_wall_s"] / max(r["num_batches"], 1)
            * 1e6,
            "epoch_wall_s": round(r["epoch_wall_s"], 3),
            "host_exposed_s": round(r["host_exposed_s"], 3),
            "overlap": round(r["overlap_fraction"], 3),
            "hbm_per_device_mib":
                round(r["hbm_per_device_bytes"] / 2**20, 2),
        })
    rows.append({
        "name": "speedup",
        "us_per_call": 0.0,
        "async_over_serial": round(speedup, 3),
    })
    audit = payload["comm_audit"]
    rows.append({
        "name": "comm_audit",
        "us_per_call": 0.0,
        "programs": len(audit["rows"]),
        "ok": audit["ok"],
    })
    return rows


# ---------------------------------------------------------------------- #
# Sharded entity table: gather+exchange time, table bytes per device
# ---------------------------------------------------------------------- #
GATE_RATIO = 1.5   # max allowed 2-shard gather+exchange / dense gather —
#   the regression bar benchmarks/run.py enforces (ROADMAP open item 2:
#   the old masked-sum chain sat at 3x)


def _time_gather(fn, *args, iters: int = 30) -> float:
    import jax
    fn(*args)[0].block_until_ready()           # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


QUANT_BYTES_RATIO_LIMIT = 0.3   # int8 table bytes / fp32 bytes per device
#   — closed form (d·1 + 4) / (d·4) = 0.266 at d=64; gated so a storage
#   regression (e.g. accidentally materializing fp32 rows) cannot land
QUANT_MRR_DRIFT_LIMIT = 0.02    # |MRR(int8) - MRR(fp32)| on the sharded
#   eval — the documented accuracy cost of row-wise symmetric int8 with
#   pow2 scales (per-element error <= scale/2); measured drift on the
#   quick synthetic eval is ~1e-3


def _quant_eval_drift(quick: bool, shards_out: List[Dict]) -> Dict:
    """Measure the int8 table's end-to-end accuracy cost: filtered MRR of
    the 2-shard sharded eval over the quantized table vs the identical
    eval over the fp32 table, same embeddings, same filter index.  Gated
    by ``benchmarks/run.py`` together with the per-device bytes ratio."""
    from repro.core.graph import make_synthetic_kg, split_train_valid_test
    from repro.eval import CSRFilterIndex, ranking_metrics

    n_ent, n_rel, n_edge = (2000, 8, 12_000) if quick else \
        (10_000, 24, 80_000)
    d = 32 if quick else 64
    kg = make_synthetic_kg(n_ent, n_rel, n_edge, seed=0)
    splits = split_train_valid_test(kg)
    graphs = [g.with_inverse_relations() for g in splits.values()]
    csr = CSRFilterIndex.build(graphs)
    rng = np.random.default_rng(1)
    emb = rng.normal(size=(n_ent, d)).astype(np.float32)
    dparams = {"rel_diag":
               rng.normal(size=(2 * n_rel, d)).astype(np.float32)}
    test = splits["test"].with_inverse_relations().triplets()[:256]
    m_fp32 = ranking_metrics(emb, dparams, test, csr, num_shards=2)
    m_int8 = ranking_metrics(emb, dparams, test, csr, num_shards=2,
                             table_dtype="int8")
    two = next(r for r in shards_out if r["num_shards"] == 2)
    return {
        "bytes_ratio_limit": QUANT_BYTES_RATIO_LIMIT,
        "bytes_ratio_2shard": two["quant_bytes_ratio"],
        "mrr_drift_limit": QUANT_MRR_DRIFT_LIMIT,
        "mrr_fp32": round(m_fp32["mrr"], 6),
        "mrr_int8": round(m_int8["mrr"], 6),
        "mrr_drift": round(abs(m_int8["mrr"] - m_fp32["mrr"]), 6),
        "eval": {"entities": n_ent, "dim": d, "test_triplets": len(test)},
    }


def _zipf_ids(rng, v: int, batch: int, a: float = 1.3) -> np.ndarray:
    """Skewed gather ids on the workload shape KGE batches actually have:
    Zipf-ranked popularity over a random entity permutation (so the hot
    set is not the contiguous low-id block — dedup wins must come from
    repetition, not shard locality)."""
    ranks = (rng.zipf(a, size=batch) - 1) % v
    return rng.permutation(v)[ranks].astype(np.int32)


def run_embedding(quick: bool = True) -> List[Dict]:
    """Dense replicated gather vs shard-local gather + exchange at 1-8
    model shards (simulated mesh), three variants per shard count:

    * ``fused`` — the flat-index fused gather (the default exchange);
    * ``chain`` — the original take → mask → sum chain (the PR-2 path the
      fused kernel replaced; kept as the regression reference);
    * ``dedup`` — fused over the unique-id plan + on-device expansion.

    ``sharded_over_dense_ratio`` (fused / dense) is the gated headline:
    ``benchmarks/run.py`` exits non-zero when the 2-shard ratio exceeds
    ``GATE_RATIO``.  A zipfian id case measures dedup on skewed batches.
    Per-device table bytes must shrink ∝ 1/num_shards — that is the
    capacity the sharding buys.

    Each shard count also measures the quantized (int8) table: the
    fused-dequant gather time, the per-device bytes
    (``rows·(d + 4)`` — codes plus the f32 scale sidecar, gated at
    ``QUANT_BYTES_RATIO_LIMIT`` x fp32) and the closed-form exchange
    wire bytes per row; a top-level ``quant`` section measures the
    end-to-end MRR drift of the int8 sharded eval vs fp32 (gated at
    ``QUANT_MRR_DRIFT_LIMIT``)."""
    import jax
    import jax.numpy as jnp
    from repro.sharding.embedding import (
        QuantizedTableLayout, ShardedTableLayout, plan_local_gather,
        plan_unique_gather, quantize_rows, shard_table, sharded_gather,
        sharded_dequant_gather,
    )

    v, d = (20_000, 64) if quick else (200_000, 128)
    batch = 4096
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    ids = rng.integers(0, v, size=batch).astype(np.int32)
    zipf = _zipf_ids(rng, v, batch)

    dense_us = _time_gather(
        jax.jit(lambda t, i: (t[i],)), table, jnp.asarray(ids)) * 1e6

    fused_fn = jax.jit(lambda t, i, o: (sharded_gather(t, i, o),))
    quant_fn = jax.jit(lambda c, sc, i, o: (
        sharded_dequant_gather(c, sc, i, o),))
    chain_fn = jax.jit(lambda t, i, o: (
        sharded_gather(t, i, o, exchange="masked_sum"),))
    dedup_fn = jax.jit(lambda t, i, o, inv: (
        sharded_gather(t, i, o, inverse=inv),))

    def time_variants(layout, sh, batch_ids):
        li, ow = plan_local_gather(layout, batch_ids)
        li, ow = jnp.asarray(li), jnp.asarray(ow)
        ul, uo, inv = plan_unique_gather(layout, batch_ids)
        out = {
            "fused_us": _time_gather(fused_fn, sh, li, ow) * 1e6,
            "chain_us": _time_gather(chain_fn, sh, li, ow) * 1e6,
            "dedup_us": _time_gather(
                dedup_fn, sh, jnp.asarray(ul), jnp.asarray(uo),
                jnp.asarray(inv)) * 1e6,
            "unique_ids": int(len(np.unique(batch_ids))),
            "plan_slots": int(ul.shape[1]),
        }
        return out

    shards_out = []
    for s in (1, 2, 4, 8):
        layout = ShardedTableLayout(v, s)
        sh = shard_table(table, layout)
        uni = time_variants(layout, sh, ids)
        zip_ = time_variants(layout, sh, zipf)
        # quantized (int8) variant: same gather plan over the int8 code
        # stack + per-row f32 scales, dequant fused into the gather —
        # per-device bytes drop to rows·(d·1 + 4) and only int8 codes
        # (plus the 4-byte scale sidecar) would cross the wire
        codes, scales = quantize_rows(sh)
        li, ow = plan_local_gather(layout, ids)
        quant_us = _time_gather(
            quant_fn, codes, scales, jnp.asarray(li), jnp.asarray(ow)) * 1e6
        q_bytes = QuantizedTableLayout(v, s).bytes_per_shard(d)
        shards_out.append({
            "num_shards": s,
            "gather_exchange_us": round(uni["fused_us"], 2),
            "chain_exchange_us": round(uni["chain_us"], 2),
            "dedup_gather_us": round(uni["dedup_us"], 2),
            "sharded_over_dense_ratio":
                round(uni["fused_us"] / max(dense_us, 1e-9), 3),
            "unique_ids": uni["unique_ids"],
            "zipf": {
                "gather_exchange_us": round(zip_["fused_us"], 2),
                "dedup_gather_us": round(zip_["dedup_us"], 2),
                "unique_ids": zip_["unique_ids"],
                "plan_slots": zip_["plan_slots"],
            },
            "table_bytes_per_device": layout.bytes_per_shard(d),
            "rows_per_shard": layout.rows_per_shard,
            "quant_gather_us": round(quant_us, 2),
            "quant_table_bytes_per_device": q_bytes,
            "quant_bytes_ratio":
                round(q_bytes / layout.bytes_per_shard(d), 4),
            # closed-form wire bytes per gathered row on the exchange
            "wire_bytes_per_row": d * 4,
            "quant_wire_bytes_per_row": d * 1 + 4,
        })

    payload = {
        "bench": "embedding",
        "table": {"entities": v, "dim": d, "batch_gather": batch,
                  "dense_bytes": v * d * 4, "quick": quick},
        "dense_gather_us": round(dense_us, 2),
        "gate_max_2shard_ratio": GATE_RATIO,
        "sharded": shards_out,
        "quant": _quant_eval_drift(quick, shards_out),
    }
    with open(EMBED_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    rows = [{"name": "dense", "us_per_call": round(dense_us, 2),
             "table_mib_per_device": round(v * d * 4 / 2**20, 2)}]
    for r in shards_out:
        rows.append({
            "name": f"sharded_{r['num_shards']}",
            "us_per_call": r["gather_exchange_us"],
            "over_dense": r["sharded_over_dense_ratio"],
            "chain_us": r["chain_exchange_us"],
            "dedup_us": r["dedup_gather_us"],
            "zipf_dedup_us": r["zipf"]["dedup_gather_us"],
            "table_mib_per_device":
                round(r["table_bytes_per_device"] / 2**20, 2),
            "quant_us": r["quant_gather_us"],
            "quant_mib_per_device":
                round(r["quant_table_bytes_per_device"] / 2**20, 2),
        })
    q = payload["quant"]
    rows.append({"name": "quant_mrr_drift",
                 "us_per_call": 0.0,
                 "mrr_fp32": q["mrr_fp32"], "mrr_int8": q["mrr_int8"],
                 "drift": q["mrr_drift"], "limit": q["mrr_drift_limit"]})
    return rows


if __name__ == "__main__":
    print("\n".join(emit(run(), "pipeline")))
    print("\n".join(emit(run_embedding(), "embedding")))
