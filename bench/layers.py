"""Per-layer breakdown of a training cell's step, from the program's own
named scopes, host spans and set-up stage counters.

    python3 bench/layers.py --workload <cell> --seed <n> [--epochs 5]

Builds the cell's trainer as ``bench/drivers/train.py`` does,
runs two epochs to compile and warm up, reads the compiled step's HLO text
(``KGETrainer.lower_step``), then records a profiler trace of ``--epochs``
epochs inside a ``bench.window`` span and prints one JSON line:

* ``scope_ms_per_step``: the step module's device time by ``kge.*`` scope
  (``bench/scopes.py``), in ms per step, and ``unscoped_share`` (%);
* ``step_module_share_of_busy`` (%): the step module's device time over the
  window's busy time (``bench/trace.py``);
* ``idle_gaps``: the window's idle time by the innermost host span open;
* ``setup_stage_s``: the set-up stages the program reported
  (``bench/stages.py``) and ``host_prep_s``, the trainer's construction.

Device numbers come only from a TPU: ``--rehearse`` shrinks the cell and
runs on the CPU, and then prints ``null`` for each of them.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def breakdown(args) -> dict:
    benchmark = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = run.cell_spec(benchmark, args.workload)
    cfg = run.load_json(os.path.join(BENCH, "configs",
                                     f"{spec['config']}.json"))
    traffic = run.load_json(os.path.join(BENCH, "traffic",
                                         f"{spec['traffic']}.json"))
    if traffic["driver"] != "train":
        raise run.Error(f"{args.workload} is not a training cell")
    if args.rehearse:
        cfg = run.apply_rehearsal(cfg)
    jax = run.start_jax(args.rehearse)
    devices = jax.devices()
    tpu = devices[0].platform == "tpu"
    if not (tpu or args.rehearse):
        raise run.Error("JAX finds no TPU; --rehearse runs on the CPU")
    chips = int(spec["chips"])

    from bench import scopes, stages, trace
    train = run.load_module("drivers", "train")
    ctx = run.Context(types.SimpleNamespace(seed=args.seed, seconds=0,
                                            trace=1),
                      spec, cfg, traffic, {})
    data, trainer, host_prep_s = train.build_trainer(ctx)
    train.start(ctx, trainer, data)
    for _ in range(2):
        trainer.train_epoch()
    module, by_instruction = scopes.instruction_scopes(
        trainer.lower_step().compile().as_text())

    session = trace.Session(chips)
    with session:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            steps = sum(trainer.train_epoch()["num_batches"]
                        for _ in range(args.epochs))
    try:
        from jax.profiler import ProfileData
        path = glob.glob(os.path.join(session.dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        profile = ProfileData.from_file(path)
        reduced = trace.reduce(profile, chips)
        lo, hi = next(s[1:] for _, sp in trace.host_spans(profile)
                      for s in sp if s[0] == trace.WINDOW_SPAN)
        layers = scopes.attribute(profile, chips, lo, hi, module,
                                  by_instruction)
    finally:
        shutil.rmtree(session.dir, ignore_errors=True)
        trainer.close()

    step_s = layers["step_module_s"]
    out = {"workload": args.workload, "seed": args.seed, "steps": steps,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind, "count": chips},
           "scope_ms_per_step": None, "unscoped_share": None,
           "step_module_share_of_busy": None}
    if tpu and step_s > 0:
        out["scope_ms_per_step"] = {k: 1000 * v / steps
                                    for k, v in layers["scope_s"].items()}
        out["unscoped_share"] = (100 * layers["scope_s"][scopes.UNSCOPED]
                                 / step_s)
        out["step_module_share_of_busy"] = 100 * step_s / reduced["busy_s"]
    if tpu:
        out["busy_s"] = reduced["busy_s"]
        out["idle_gaps"] = reduced["breakdown"]["idle_gaps"]
    out["window_s"] = reduced["window_s"]
    out["setup_stage_s"] = dict(stages.seconds)
    out["host_prep_s"] = host_prep_s
    return out


def main(argv=None) -> int:
    try:
        line = breakdown(parse(argv))
    except run.Error as exc:
        print(f"layers: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
