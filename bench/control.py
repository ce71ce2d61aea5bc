"""Readings that set the limits of ``correct`` for a training cell: the
program's, its control's and its faults', over many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

The benchmark's own runs never run this.  For each seed it prints one JSON
line with the numbers ``bench/run.py`` compares, read three ways:

* ``program``: the timed path against the reference (float32 at
  ``highest`` precision), as a run reads them;
* ``control``: the reference computed in bfloat16 in the program's
  place, against the same reference;
* ``faults``: the program with its step broken underneath, one fault at
  a time: ``unchanged`` returns the state it was given, ``half`` leaves
  out the second half of every trainer's rows and takes the mean over the
  rest.

Set-up (dataset and trainer) is paid once; each seed draws its own
weights, negatives and dropout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as harness_run  # noqa: E402


def _ctx(args, seed):
    ns = argparse.Namespace(workload=args.workload, seed=seed,
                            seconds=args.seconds, trace=0,
                            rehearse=args.rehearse)
    bm = harness_run.load_json(os.path.join(harness_run.ROOT,
                                            "BENCHMARK.json"))
    spec = harness_run.cell_spec(bm, args.workload)
    cfg = harness_run.load_json(os.path.join(
        harness_run.BENCH, "configs", f"{spec['config']}.json"))
    if args.rehearse:
        cfg = harness_run.apply_rehearsal(cfg)
    traffic = harness_run.load_json(os.path.join(
        harness_run.BENCH, "traffic", f"{spec['traffic']}.json"))
    return harness_run.Context(ns, spec, cfg, traffic, {})


# ---------------------------------------------------------------------- #
def half_rows(batch, full: bool):
    """The batch with the second half of every trainer's rows masked out."""
    import jax.numpy as jnp
    key = "core_edge_mask" if full else "triplet_mask"
    mask = batch[key]
    keep = jnp.arange(mask.shape[1]) < mask.shape[1] // 2
    return {**batch, key: mask & keep[None, :]}


def train_seeds(args, seeds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    drv = harness_run.load_module("drivers", "train")
    ctx = _ctx(args, seeds[0])
    data, trainer, _ = drv.build_trainer(ctx)
    full = trainer._fullgraph
    orig = trainer.step
    common = dict(mode="full" if full else "minibatch",
                  num_negatives=ctx.cfg["num_negatives"],
                  dropout=ctx.cfg["dropout"], lr=ctx.cfg["learning_rate"],
                  adam=ctx.cfg["adam"])
    b1 = ctx.cfg["adam"]["b1"]

    def program_run(seed, step):
        ctx.seed = seed
        params0 = drv.start(ctx, trainer, data)
        trainer.step = step
        rec = drv.StepRecorder(trainer, ctx.traffic["checked_steps"])
        while len(rec.steps) < rec.n:
            trainer.train_epoch()
        rec.detach()
        trainer.step = orig
        prog = {"losses": [s["loss"] for s in rec.steps],
                "grad1": jax.tree_util.tree_map(
                    lambda m: np.asarray(m) / (1 - b1), rec.mu1),
                "params": rec.params_n}
        return params0, rec.steps, prog

    faults = {
        "unchanged": lambda p, o, b, k: (p, o, orig(p, o, b, k)[2]),
        "half": lambda p, o, b, k: orig(p, o, half_rows(b, full), k),
    }
    for seed in seeds:
        params0, steps, prog = program_run(seed, orig)
        ref = ctx.reference.replay(params0, data["features"], steps,
                                   **common, dtype=jnp.float32)
        ctl = ctx.reference.replay(params0, data["features"], steps,
                                   **common, dtype=jnp.bfloat16)
        out = {"seed": seed,
               "program": drv.compare(prog, ref, params0),
               "control": drv.compare(ctl, ref, params0),
               "faults": {}}
        for name, step in faults.items():
            p0, _, fp = program_run(seed, step)
            out["faults"][name] = drv.compare(fp, ref, p0)
        print(json.dumps(out), flush=True)
    trainer.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    jax = harness_run.start_jax(args.rehearse)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("control: JAX finds no TPU", file=sys.stderr)
        return 2
    bm = harness_run.load_json(os.path.join(harness_run.ROOT,
                                            "BENCHMARK.json"))
    spec = harness_run.cell_spec(bm, args.workload)
    traffic = harness_run.load_json(os.path.join(
        harness_run.BENCH, "traffic", f"{spec['traffic']}.json"))
    if traffic["driver"] != "train":
        print(f"control: no readings for driver {traffic['driver']!r}",
              file=sys.stderr)
        return 2
    train_seeds(args, seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
