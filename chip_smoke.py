"""Drive the R-GCN trainer, evaluation and top-k server once on one TPU chip.

The paper's FB15k-237 configuration (``RGCN_FB15K237``: d = 75, 2 bases,
2 hops, 8 trainers) at ``scale=1.0`` — 14,541 entities, 237 relations and
272,115 edges generated from ``--seed`` — with random initial weights,
through the entry points ``repro.launch.train`` and ``repro.launch.serve``
use, in one process:

  a  full-graph training, a few steps (``KGETrainer.fit``);
  b  mini-batch training over a 2-shard int8 entity table: the fused
     gather, fused-dequant gather and scatter-add kernels run forward and
     backward, with batch donation on;
  c  filtered test-split evaluation through the ``kge_score`` kernel
     (``KGETrainer.evaluate``);
  d  filtered top-k requests at k = 10 through ``KGEServeEngine`` over a
     ``ShardedKGEServer`` of phase a's table, with 1 and 2 shards.

Each phase prints one JSON line: its shapes, its set-up seconds (compile
included) and wall seconds on the host clock (not device metrics), its
loss or metrics, and a check against a reference:

  a, b  the trainer's first step run on the chip and again on
        ``jax.devices("cpu")`` from the same state, batch and keys: the
        losses and the parameter updates must agree;
  c     the chip's metrics must lie inside the interval a float64 host
        ranking of the same embeddings allows for TPU matmul rounding;
  d     the served tails and scores must equal a dense ``jax.lax.top_k``
        over the same kernel scores.

``--chips 4`` runs only one comparison: the shard_map trainer on a 2x2
data x model mesh (2 table shards, ``psum_scatter`` exchange) against the
simulated step on one device, for the same 3 full-graph steps.

The last line of standard output is ``{"ok": true, "device": {...}}`` with
the device JAX reports; any failed phase exits non-zero.  Without a TPU the
script exits non-zero before any work, unless ``--rehearse`` shrinks the
graph for a CPU run (the last line then names the platform it ran on).

  python chip_smoke.py                      # one chip
  python chip_smoke.py --chips 4            # 2x2 mesh vs simulated step
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))

# the full run keeps RGCN_FB15K237's widths and 8 trainers; the rehearsal
# cuts the graph to 200 entities, 8 relations and 2,000 edges, and the
# trainers and steps to what a CPU runs in seconds
SIZES = {
    "full": {"scale": 1.0, "trainers": 8, "steps": 3, "batch_size": 8192},
    "rehearse": {"scale": 0.001, "trainers": 4, "steps": 2,
                 "batch_size": 256},
}
SERVE_REQUESTS = 24
SERVE_SLOTS = 8
TOPK = 10
# chip vs host agreement of the first step: relative loss difference, and
# the share of parameter entries whose first update differs by more than
# half a learning rate (Adam's first step is +-lr per entry, so such an
# entry moved the other way)
LOSS_RTOL = 2.0 ** -7
UPDATE_FLIP_MAX = 0.01
# bound on one score's rounding on the chip, relative to sum_i |q_i c_i|:
# one bf16 rounding of each factor (2 * 2^-9) plus fp32 accumulation over
# d <= 128 terms (2^-17) is under 2^-7
SCORE_REL_ERR = 2.0 ** -7


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the 2x2 shard_map trainer vs the "
                         "simulated step")
    ap.add_argument("--rehearse", action="store_true",
                    help="shrink the graph and run on whatever platform "
                         "JAX finds (CPU here)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def _emit(rec: dict) -> None:
    print(json.dumps(rec, default=float), flush=True)


def _seconds(t0: float) -> float:
    return time.perf_counter() - t0


# ---------------------------------------------------------------------- #
# training phases
# ---------------------------------------------------------------------- #
def _first_step_check(jax, np, trainer) -> dict:
    """The trainer's next step from the same state, batch and keys, on
    the chip and on the host CPU.  Returns the chip's compiled step's
    Pallas call count, both losses and the update disagreement."""
    host = jax.device_get(trainer.next_step_args())
    lr = trainer.cfg.learning_rate
    out = {}
    for name, dev in (("chip", jax.devices()[0]),
                      ("cpu", jax.devices("cpu")[0])):
        args = jax.device_put(host, dev)
        if name == "chip":
            compiled = trainer.step.lower(*args).compile()
            out["pallas_calls"] = compiled.as_text().count(
                "tpu_custom_call")
            params, _, metrics = compiled(*args)
        else:
            params, _, metrics = trainer.step(*args)
        out[name] = (float(metrics["loss"]), jax.device_get(params))
    (l_chip, p_chip), (l_cpu, p_cpu) = out["chip"], out["cpu"]
    p0 = jax.tree_util.tree_leaves(host[0])
    flips = total = 0
    for a, b, c in zip(jax.tree_util.tree_leaves(p_chip),
                       jax.tree_util.tree_leaves(p_cpu), p0):
        du = np.abs((np.asarray(a) - c) - (np.asarray(b) - c))
        flips += int(np.sum(du > lr / 2))
        total += du.size
    flip_share = flips / total
    loss_rel = abs(l_chip - l_cpu) / abs(l_cpu)
    return {
        "pallas_calls": out["pallas_calls"],
        "loss_chip": l_chip, "loss_cpu": l_cpu, "loss_rel_diff": loss_rel,
        "update_flip_share": flip_share,
        "ok": bool(loss_rel <= LOSS_RTOL and flip_share <= UPDATE_FLIP_MAX),
    }


def _train_phase(jax, np, name: str, splits, cfg) -> tuple:
    from repro.training import KGETrainer

    t0 = time.perf_counter()
    trainer = KGETrainer(splits, cfg)
    prep_s = _seconds(t0)
    t0 = time.perf_counter()
    check = _first_step_check(jax, np, trainer)
    setup_s = _seconds(t0)
    t0 = time.perf_counter()
    hist = trainer.fit(epochs=cfg.epochs)
    wall_s = _seconds(t0)
    table = trainer.params["entity_embedding"]
    rec = {
        "phase": name,
        "shapes": {
            "entities": trainer.train_kg.num_entities,
            "relations": trainer.train_kg.num_relations,
            "train_edges_with_inverse": trainer.train_kg.num_edges,
            "hidden_dim": cfg.hidden_dim, "trainers": cfg.num_trainers,
            "batch_size": cfg.batch_size,
            "entity_table": list(table.shape),
            "table_dtype": cfg.table_dtype,
            "steps": int(sum(h["num_batches"] for h in hist)),
        },
        "preprocess_s": prep_s,
        "setup_s": setup_s, "wall_s": wall_s,
        "loss": [h["loss"] for h in hist],
        "check": check, "ok": check["ok"],
    }
    rec["ok"] = bool(rec["ok"] and all(np.isfinite(rec["loss"])))
    return trainer, rec


# ---------------------------------------------------------------------- #
# evaluation
# ---------------------------------------------------------------------- #
def _reference_rank_bounds(np, emb, rel_diag, triplets, fidx):
    """float64 host ranking of ``triplets`` (DistMult, filtered, mean
    rank) as an interval: the lowest and highest rank a chip may report
    when each score may be off by ``SCORE_REL_ERR * sum_i |q_i c_i|``."""
    from repro.eval.ranking import FILTER_BIAS

    emb64 = emb.astype(np.float64)
    lo, hi = [], []
    for start in range(0, len(triplets), 512):
        t = triplets[start: start + 512]
        q = emb64[t[:, 0]] * rel_diag.astype(np.float64)[t[:, 1]]
        s = q @ emb64.T
        err = SCORE_REL_ERR * (np.abs(q) @ np.abs(emb64).T)
        rows = np.arange(len(t))
        s_true, e_true = s[rows, t[:, 2]], err[rows, t[:, 2]]
        live = fidx.bias(t, emb.shape[0]) != FILTER_BIAS
        live[rows, t[:, 2]] = False          # the true tail itself
        margin = err + e_true[:, None]
        gap = s - s_true[:, None]
        lo.append(1 + np.sum(live & (gap > margin), axis=1))
        hi.append(1 + np.sum(live & (gap >= -margin), axis=1))
    return np.concatenate(lo), np.concatenate(hi)


def _eval_phase(jax, np, trainer) -> dict:
    from repro.eval.ranking import CSRFilterIndex

    t0 = time.perf_counter()
    trainer.evaluate("test")
    first_s = _seconds(t0)
    t0 = time.perf_counter()
    metrics = trainer.evaluate("test")
    wall_s = _seconds(t0)

    splits = trainer.splits
    test = splits["test"]
    n_rel = splits["train"].num_relations
    fidx = CSRFilterIndex.build(
        [splits[s].with_inverse_relations()
         for s in ("train", "valid", "test")])
    emb = np.asarray(trainer.encode_all_entities())
    rel_diag = np.asarray(trainer.params["decoder"]["rel_diag"])
    fwd = test.triplets()
    inv = np.stack([test.dst, test.rel + n_rel, test.src], axis=1)
    bounds = {k: [] for k in ("mrr", "hits@1", "hits@3", "hits@10")}
    for trip in (fwd, inv):
        lo, hi = _reference_rank_bounds(np, emb, rel_diag, trip, fidx)
        bounds["mrr"].append((np.mean(1.0 / hi), np.mean(1.0 / lo)))
        for k in (1, 3, 10):
            bounds[f"hits@{k}"].append(
                (np.mean(hi <= k), np.mean(lo <= k)))
    check = {}
    ok = True
    for key, per_dir in bounds.items():
        low = 0.5 * (per_dir[0][0] + per_dir[1][0])
        high = 0.5 * (per_dir[0][1] + per_dir[1][1])
        got = metrics[f"test_{key}"]
        inside = low <= got <= high
        ok &= bool(inside)
        check[key] = {"chip": got, "ref_low": low, "ref_high": high,
                      "inside": bool(inside)}
    return {
        "phase": "c_eval",
        "shapes": {"queries": 2 * test.num_edges,
                   "candidates": emb.shape[0], "hidden_dim": emb.shape[1]},
        "setup_s": first_s - wall_s, "wall_s": wall_s,
        "metrics": metrics, "check": check, "ok": ok,
    }


# ---------------------------------------------------------------------- #
# serving
# ---------------------------------------------------------------------- #
def _serve_phase(jax, np, trainer) -> dict:
    import jax.numpy as jnp

    from repro.eval.ranking import CSRFilterIndex
    from repro.models.decoders import get_decoder
    from repro.serving import KGEServeEngine, ShardedKGEServer

    splits = trainer.splits
    fidx = CSRFilterIndex.build(
        [splits[s].with_inverse_relations()
         for s in ("train", "valid", "test")])
    emb = np.asarray(trainer.encode_all_entities())
    dparams = trainer.params["decoder"]
    decoder = trainer.cfg.decoder
    test = splits["test"].triplets()[:SERVE_REQUESTS]
    heads, rels = test[:, 0], test[:, 1]

    # reference: dense lax.top_k over the same kernel scores, unsharded
    query = np.stack([heads, rels, np.full(len(heads), -1)], axis=1)
    bias = fidx.bias(query, emb.shape[0])
    scores = get_decoder(decoder).rank_scores(
        dparams, jnp.asarray(emb[heads]), jnp.asarray(rels, jnp.int32),
        jnp.asarray(emb), jnp.asarray(bias))
    want_v, want_t = map(np.asarray, jax.lax.top_k(scores, TOPK))

    rec = {"phase": "d_serve",
           "shapes": {"entities": emb.shape[0], "hidden_dim": emb.shape[1],
                      "requests": len(heads), "slots": SERVE_SLOTS,
                      "k": TOPK, "filtered": True},
           "runs": [], "ok": True}
    for shards in (1, 2):
        server = ShardedKGEServer(emb, dparams, decoder, num_shards=shards,
                                  filter_index=fidx)
        engine = KGEServeEngine(server, slots=SERVE_SLOTS, max_k=TOPK,
                                filtered=True)
        t0 = time.perf_counter()
        engine.submit(int(heads[0]), int(rels[0]), k=TOPK)
        engine.run()
        setup_s = _seconds(t0)
        reqs = [engine.submit(int(h), int(r), k=TOPK)
                for h, r in zip(heads, rels)]
        t0 = time.perf_counter()
        engine.run()
        wall_s = _seconds(t0)
        got_t = np.stack([r.tails for r in reqs])
        got_v = np.stack([r.scores for r in reqs])
        same = bool((got_t == want_t).all() and (got_v == want_v).all())
        rec["runs"].append({"shards": shards, "setup_s": setup_s,
                            "wall_s": wall_s,
                            "tails_equal_dense": bool((got_t == want_t).all()),
                            "scores_equal_dense": bool(
                                (got_v == want_v).all())})
        rec["ok"] &= same
    return rec


# ---------------------------------------------------------------------- #
# four chips: shard_map trainer vs the simulated step
# ---------------------------------------------------------------------- #
def _spmd_phase(jax, np, splits, base) -> dict:
    from repro.training import KGETrainer

    runs = {}
    for mode, spmd, exchange in (("spmd", True, "psum_scatter"),
                                 ("sim", False, None)):
        cfg = dataclasses.replace(base, num_table_shards=2, spmd=spmd,
                                  gather_exchange=exchange)
        tr = KGETrainer(splits, cfg)
        t0 = time.perf_counter()
        hist = tr.fit()
        runs[mode] = {
            "mesh": None if tr.mesh is None else dict(tr.mesh.shape),
            "wall_s": _seconds(t0),
            "loss": [h["loss"] for h in hist],
            "params": jax.device_get(tr.params),
        }
        tr.close()
    loss_diff = max(abs(a - b) for a, b in zip(runs["spmd"]["loss"],
                                                runs["sim"]["loss"]))
    param_diff = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree_util.tree_leaves(runs["spmd"]["params"]),
                        jax.tree_util.tree_leaves(runs["sim"]["params"])))
    return {
        "phase": "spmd_vs_sim",
        "shapes": {"mesh": runs["spmd"]["mesh"], "table_shards": 2,
                   "exchange": "psum_scatter", "steps": base.epochs},
        "wall_s": {m: r["wall_s"] for m, r in runs.items()},
        "loss": {m: r["loss"] for m, r in runs.items()},
        "max_loss_diff": loss_diff, "max_param_diff": param_diff,
        "ok": loss_diff == 0.0 and param_diff == 0.0,
    }


# ---------------------------------------------------------------------- #
def main(argv=None) -> int:
    args = _parse(argv)
    if args.rehearse and args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    import jax
    import numpy as np

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX finds no TPU (only {devices[0].platform} "
              "devices); --rehearse runs the shrunken CPU rehearsal",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.configs import RGCN_FB15K237
    from repro.data import synthetic_fb15k
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # the host reference replays a step that donates its batch; donation
    # is a no-op on the CPU backend and says so
    warnings.filterwarnings("ignore", message="Some donated buffers")
    size = SIZES["rehearse" if args.rehearse else "full"]
    t0 = time.perf_counter()
    splits = synthetic_fb15k(scale=size["scale"], seed=args.seed)
    _emit({"phase": "data", "scale": size["scale"], "seed": args.seed,
           "entities": splits["train"].num_entities,
           "relations": splits["train"].num_relations,
           "edges": sum(g.num_edges for g in splits.values()),
           "setup_s": _seconds(t0), "compile_cache": cache_dir})
    base = dataclasses.replace(RGCN_FB15K237, seed=args.seed,
                               num_trainers=size["trainers"],
                               epochs=size["steps"])

    ok = True

    def run(fn, *a):
        nonlocal ok
        try:
            rec = fn(*a)
        except Exception:
            traceback.print_exc()
            ok = False
            return None
        _emit(rec)
        ok &= bool(rec["ok"])
        return rec

    if args.chips == 4:
        run(_spmd_phase, jax, np, splits, base)
    else:
        trainers = {}

        def train(name, cfg):
            trainers[name], rec = _train_phase(jax, np, name, splits, cfg)
            return rec

        run(train, "a_fullgraph_train", base)
        run(train, "b_minibatch_int8_2shard_train",
            dataclasses.replace(base, epochs=1,
                                batch_size=size["batch_size"],
                                num_table_shards=2, table_dtype="int8"))
        if "b_minibatch_int8_2shard_train" in trainers:
            trainers.pop("b_minibatch_int8_2shard_train").close()
        trainer = trainers.get("a_fullgraph_train")
        if trainer is None:
            print("chip_smoke: phases c and d need phase a's trainer",
                  file=sys.stderr)
            ok = False
        else:
            run(_eval_phase, jax, np, trainer)
            run(_serve_phase, jax, np, trainer)
            trainer.close()

    if not ok:
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
