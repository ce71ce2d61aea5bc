import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): for every (architecture × input shape ×
mesh), ``jit(step).lower(**abstract_inputs).compile()`` must succeed on the
production meshes — 16×16 single-pod and 2×16×16 multi-pod — and emit the
memory / cost / collective numbers the roofline (§Roofline) reads.
(No ``from __future__`` import here: the XLA_FLAGS lines above must stay the
first statements in the file.)

Run:  PYTHONPATH=src python -m repro.launch.dryrun \
          [--arch all] [--shape all] [--mesh single,multi] \
          [--out experiments/dryrun.jsonl] [--force]

Results are appended incrementally (one JSON per line); existing (arch,
shape, mesh) keys are skipped unless --force.

NOTE the XLA_FLAGS assignment above MUST precede every jax import — jax
locks the device count at first init.  Only this entry point sets it; tests
and benchmarks see the real single CPU device.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED
from repro.launch import specs as S
from repro.launch.mesh import chip_peaks, make_production_mesh
from repro.launch.steps import (
    make_prefill_step, make_serve_step, make_train_step,
)
from repro.sharding.context import mesh_context
from repro.sharding.hlo_analysis import analyze_hlo, total_collective_bytes
from repro.sharding.rules import (
    batch_shardings, cache_shardings, opt_state_shardings, param_shardings,
)
from repro.training.optimizer import adam

# The chip the production meshes model: the roofline divides by ITS peaks
# (the lowering itself runs on forced host devices).
TARGET_DEVICE_KIND = "TPU v5 lite"


def lower_rgcn(mesh_kind: str, overrides: str = "") -> Dict:
    """The paper's own configuration at pod scale: one self-sufficient
    partition per chip (data-parallel over ALL mesh axes — the paper's
    trainer axis), RGCN + DistMult + constraint-based negatives, gradient
    AllReduce via pmean inside shard_map.  Partition shapes follow the
    ogbl-citation2 statistics (Table 2) extrapolated to 256/512 partitions;
    features ship WITH the partition (self-sufficiency: no remote gathers,
    exactly §3.2)."""
    import jax.numpy as jnp
    from repro.models import KGEConfig, RGCNConfig, init_kge_params
    from repro.models.rgcn import rgcn_encode
    from repro.models import decoders
    from repro.core.negative import constraint_based_negatives, mix_pos_neg
    from repro.training.distributed import make_spmd_train_step

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    axes = tuple(mesh.axis_names)          # trainers = ALL axes
    n_chips = int(np.prod(list(mesh.shape.values())))
    V_MAX, E_MAX, FEAT, HID = 262_144, 1_048_576, 128, 32
    # §Perf variant: "dtype=bf16" ships features + activations in bf16
    feat_dtype = jnp.bfloat16 if "dtype=bf16" in overrides else jnp.float32
    kge_cfg = KGEConfig(rgcn=RGCNConfig(
        num_entities=2_927_963, num_relations=2, hidden_dim=HID,
        num_layers=2, num_bases=2, feature_dim=FEAT, dropout=0.0))

    params = jax.eval_shape(
        lambda: init_kge_params(jax.random.PRNGKey(0), kge_cfg))
    if "dtype=bf16" in overrides:
        # full bf16: params + features (+ therefore messages/activations)
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    sds = jax.ShapeDtypeStruct
    batch = {
        "src": sds((n_chips, E_MAX), jnp.int32),
        "rel": sds((n_chips, E_MAX), jnp.int32),
        "dst": sds((n_chips, E_MAX), jnp.int32),
        "edge_mask": sds((n_chips, E_MAX), jnp.bool_),
        "core_edge_mask": sds((n_chips, E_MAX), jnp.bool_),
        "features": sds((n_chips, V_MAX, FEAT), feat_dtype),
        "num_core_vertices": sds((n_chips,), jnp.int32),
    }

    def loss_fn(p, b, key):
        h = rgcn_encode(p, kge_cfg.rgcn, b["features"], b["src"], b["rel"],
                        b["dst"], b["edge_mask"])
        pos = jnp.stack([b["src"], b["rel"], b["dst"]], axis=1)
        neg, _ = constraint_based_negatives(
            key, pos, 1, b["num_core_vertices"])
        trip, labels = mix_pos_neg(pos, neg)
        core = b["core_edge_mask"].astype(jnp.float32)
        mask = jnp.concatenate([core, core], axis=0)
        scores = decoders.score_triplets(p["decoder"], "distmult", h, trip)
        loss = decoders.bce_loss(scores, labels, mask)
        return loss, {}

    optimizer = adam(1e-2)
    opt_state = jax.eval_shape(optimizer.init, params)
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), n_chips))
    step = make_spmd_train_step(loss_fn, optimizer, mesh, data_axes=axes)

    t0 = time.time()
    lowered = jax.jit(step).lower(params, opt_state, batch, keys)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    coll_bytes, coll_stats = total_collective_bytes(hlo_text)
    parsed = analyze_hlo(hlo_text)
    peaks = chip_peaks(TARGET_DEVICE_KIND)
    terms = {
        "compute_s": parsed["flops"] / peaks["peak_flops_bf16"],
        "memory_s": parsed["bytes"] / peaks["hbm_bw"],
        "collective_s": coll_bytes / peaks["ici_bw"],
    }
    dominant = max(terms, key=terms.get)
    return {
        "arch": "rgcn-citation2", "shape": "kg_train", "mesh": mesh_kind,
        "mode": "train", "status": "ok", "chips": n_chips,
        "overrides": overrides,
        "note": f"paper's own config: {n_chips} self-sufficient partitions, "
                "V_max=262144 E_max=1048576 per partition",
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "hlo_flops_per_device": parsed["flops"],
        "hlo_bytes_per_device": parsed["bytes"],
        "hlo_flops_raw": float(cost.get("flops", 0.0)),
        "collective_bytes_per_device": coll_bytes,
        "collective_detail": coll_stats,
        "memory": {"argument_bytes": mem.argument_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "output_bytes": mem.output_size_in_bytes},
        "model_flops_global": 0.0, "model_flops_per_device": 0.0,
        "useful_flops_ratio": None,
        "roofline": {**terms, "dominant": dominant.replace("_s", "")},
    }


def _apply_overrides(cfg, overrides: str):
    """--override "k=v,k=v" → dataclasses.replace on the ArchConfig."""
    import dataclasses as _dc
    if not overrides:
        return cfg
    kw = {}
    for item in overrides.split(","):
        k, v = item.split("=", 1)
        field = {f.name: f for f in _dc.fields(cfg)}[k]
        if field.type in ("int",):
            v = int(v)
        elif field.type in ("float",):
            v = float(v)
        elif field.type in ("bool",):
            v = v.lower() in ("1", "true")
        kw[k] = v
    return _dc.replace(cfg, **kw)


def lower_one(arch_name: str, shape_name: str, mesh_kind: str,
              sharding_mode: str = "2d", overrides: str = "") -> Dict:
    """Lower+compile one combination; returns the result record."""
    if arch_name == "rgcn-citation2":
        if shape_name != "kg_train":
            return {"arch": arch_name, "shape": shape_name,
                    "mesh": mesh_kind, "status": "skipped",
                    "note": "rgcn uses its own kg_train shape", "mode": "-"}
        return lower_rgcn(mesh_kind, overrides)
    shape = S.INPUT_SHAPES[shape_name]
    cfg, note = S.resolve_arch_for_shape(arch_name, shape_name)
    rec: Dict = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
        "mode": shape.mode, "sharding": sharding_mode, "note": note,
        "overrides": overrides, "status": "skipped",
    }
    if cfg is None:
        return rec
    cfg = _apply_overrides(cfg, overrides)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    params = S.abstract_params(cfg)
    p_sh = param_shardings(params, mesh, mode=sharding_mode)
    optimizer = adam(1e-4)

    t0 = time.time()
    with mesh_context(mesh):
        lowered = _lower(cfg, shape, mesh, params, p_sh, optimizer)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        }
    except Exception:
        mem_rec = {}

    trip = S.scan_trip_count(cfg)
    hlo_text = compiled.as_text()
    coll_bytes, coll_stats = total_collective_bytes(
        hlo_text, loop_trip_count=trip)
    parsed = analyze_hlo(hlo_text, loop_trip_count=trip)

    # raw XLA numbers (count while bodies ONCE — kept as cross-check);
    # loop-aware parsed numbers drive the roofline
    hlo_flops_raw = float(cost.get("flops", 0.0))
    hlo_bytes_raw = float(cost.get("bytes accessed", 0.0))
    hlo_flops = parsed["flops"]
    hlo_bytes = parsed["bytes"]
    mf = S.model_flops(cfg, shape)

    # roofline terms (seconds), per-device program numbers
    peaks = chip_peaks(TARGET_DEVICE_KIND)
    t_compute = hlo_flops / peaks["peak_flops_bf16"]
    t_memory = hlo_bytes / peaks["hbm_bw"]
    t_coll = coll_bytes / peaks["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    rec.update({
        "status": "ok",
        "chips": n_chips,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "hlo_flops_per_device": hlo_flops,
        "hlo_bytes_per_device": hlo_bytes,
        "hlo_flops_raw": hlo_flops_raw,
        "hlo_bytes_raw": hlo_bytes_raw,
        "collective_bytes_per_device": coll_bytes,
        "collective_detail": coll_stats,
        "scan_trip_count": trip,
        "memory": mem_rec,
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / hlo_flops
        if hlo_flops else None,
        "roofline": {**terms, "dominant": dominant.replace("_s", "")},
    })
    return rec


def _lower(cfg, shape, mesh, params, p_sh, optimizer):
    """Build the jit and lower with abstract inputs (mesh installed)."""
    if shape.mode == "train":
        opt_state = S.abstract_opt_state(params, optimizer)
        o_sh = opt_state_shardings(opt_state, p_sh, mesh)
        batch = S.abstract_batch(cfg, shape)
        b_sh = batch_shardings(batch, mesh)
        step = make_train_step(cfg, optimizer)
        return jax.jit(
            step, in_shardings=(p_sh, o_sh, b_sh),
            donate_argnums=(0, 1)).lower(params, opt_state, batch)
    if shape.mode == "prefill":
        batch = S.abstract_batch(cfg, shape)
        b_sh = batch_shardings(batch, mesh)
        step = make_prefill_step(cfg)
        return jax.jit(
            step, in_shardings=(p_sh, b_sh)).lower(params, batch)
    batch = S.abstract_batch(cfg, shape)
    b_sh = batch_shardings(batch, mesh)
    cache = S.abstract_cache(cfg, shape)
    c_sh = cache_shardings(cache, mesh)
    step = make_serve_step(cfg)
    return jax.jit(
        step, in_shardings=(p_sh, c_sh, b_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(1,)).lower(params, cache, batch)


def load_done(path: str) -> Dict:
    done = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done[(r["arch"], r["shape"], r["mesh"])] = r
                except Exception:
                    pass
    return done


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="experiments/dryrun.jsonl")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--sharding", default="2d", choices=["2d", "1d"])
    ap.add_argument("--override", default="",
                    help="ArchConfig overrides, e.g. rwkv_mode=chunked")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else args.arch.split(",")
    shapes = (list(S.INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = args.mesh.split(",")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = {} if args.force else load_done(args.out)
    failures = 0
    with open(args.out, "a") as out:
        for arch in archs:
            for shape in shapes:
                for mesh_kind in meshes:
                    key = (arch, shape, mesh_kind)
                    prev = done.get(key)
                    if prev and prev.get("status") in ("ok", "skipped"):
                        continue
                    t0 = time.time()
                    try:
                        rec = lower_one(arch, shape, mesh_kind,
                                        sharding_mode=args.sharding,
                                        overrides=args.override)
                    except Exception as e:
                        rec = {"arch": arch, "shape": shape,
                               "mesh": mesh_kind, "status": "error",
                               "error": f"{type(e).__name__}: {e}",
                               "traceback": traceback.format_exc()[-2000:]}
                        failures += 1
                    out.write(json.dumps(rec) + "\n")
                    out.flush()
                    dom = rec.get("roofline", {}).get("dominant", "-")
                    print(f"[{time.strftime('%H:%M:%S')}] {arch:>22s} "
                          f"{shape:>12s} {mesh_kind:>6s} "
                          f"{rec['status']:>7s} dom={dom} "
                          f"({time.time() - t0:.0f}s)", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
