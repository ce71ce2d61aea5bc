"""Training driver: ``KGETrainer.train_epoch`` over the configuration's
graph, repeated for the window.

Set-up builds the dataset (``bench/graphs.py``), the trainer (host
preprocessing: partition, expand, pad, budgets), the weights from
``--seed`` in one jitted call, and then drives the trainer's own
``train_epoch`` through its first ``checked_steps`` steps: the first
compiles, and all of them are recorded (the batch and keys each step is
fed, its loss, the optimizer state after the first and the parameters
after the last) for the comparison with the reference.  The same trainer
then runs the window: whole epochs until ``--seconds`` have passed, the
last one counted whole, time included.

``train_edges_per_s`` is the real positive training edges (core edges,
inverses included; no padding, no negatives) of every epoch of the
window, over the window.

A traced run also counts each step's real rows (``harness.StepProbe``)
and, once the window has closed and the peak memory is read, compiles the
step again for the shapes the window ran, so that the trace's device
operations are read by the program's named scopes (``bench/scopes.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time

import numpy as np

from bench import graphs, harness, scopes, trace


# keys of a configuration file that the harness reads itself; every other
# key names a ``TrainConfig`` field and is passed to the trainer as it is
HARNESS_KEYS = frozenset({
    "name", "source", "reference", "scale", "dataset", "matmul_precision",
    "adam", "reduced", "assumed", "rehearse"})
# ``TrainConfig`` fields the harness sets: the dataset's seed, one epoch
# per ``train_epoch`` call
SET_BY_HARNESS = frozenset({"seed", "epochs"})


def train_config(cfg: dict):
    """The ``TrainConfig`` a configuration states: every top-level key that
    names one of its fields, as it is, with ``seed`` the dataset's and
    ``epochs`` 1.  A key that is neither a field nor the harness's own is
    an error, so that a misspelt setting never falls back to a default."""
    from repro.training import TrainConfig

    fields = {f.name for f in dataclasses.fields(TrainConfig)}
    unknown = sorted(set(cfg) - HARNESS_KEYS - (fields - SET_BY_HARNESS))
    if unknown:
        raise harness.Error(
            f"configuration {cfg.get('name')!r}: {', '.join(unknown)} "
            "neither a TrainConfig field the configuration may set nor a "
            "key of the harness")
    return TrainConfig(**{k: v for k, v in cfg.items() if k in fields},
                       seed=int(cfg["dataset"]["seed"]), epochs=1)


def build_trainer(ctx):
    """The dataset and the trainer, as the configuration states them."""
    from repro.core import KnowledgeGraph
    from repro.training import KGETrainer

    tcfg = train_config(ctx.cfg)
    data = graphs.make_dataset(ctx.cfg)
    splits = {name: KnowledgeGraph(
        src=t[:, 0], rel=t[:, 1], dst=t[:, 2],
        num_entities=data["num_entities"],
        num_relations=data["num_relations"], features=data["features"])
        for name, t in data["splits"].items()}
    t0 = time.perf_counter()
    trainer = KGETrainer(splits, tcfg)
    return data, trainer, time.perf_counter() - t0


def start(ctx, trainer, data):
    """Weights, optimizer state and keys from ``--seed``.  Returns the
    initial parameters on the host."""
    import jax
    cfg = ctx.cfg
    k_w, k_t = harness.seed_keys(ctx.seed)
    params = ctx.reference.init_params(
        k_w, data["num_entities"], 2 * data["num_relations"],
        cfg["hidden_dim"], cfg["num_bases"], cfg["num_hops"],
        cfg["dataset"].get("feature_dim"))
    host = jax.device_get(params)
    trainer.params = params
    trainer.opt_state = trainer.optimizer.init(params)
    trainer._key = k_t
    if trainer.mesh is not None:
        trainer._place_state()
    if not trainer._fullgraph:
        trainer.pipeline.seed = ctx.seed
    return host


class StepRecorder:
    """Wraps ``trainer.step`` for the first ``n`` steps: the batch and keys
    each is fed (copied to the host before the step, which may donate
    them), its loss, the optimizer's first moment after step 1 and the
    parameters after step ``n``.  Unwrapped again before the window."""

    def __init__(self, trainer, n: int):
        self.trainer, self.n = trainer, n
        self.orig = trainer.step
        self.steps, self.mu1, self.params_n = [], None, None
        trainer.step = self

    def __call__(self, params, opt_state, batch, keys):
        import jax
        i = len(self.steps)
        if i >= self.n:
            return self.orig(params, opt_state, batch, keys)
        same = i > 0 and batch is self.steps[0]["device_batch"]
        host_batch = self.steps[0]["batch"] if same else \
            jax.device_get(batch)
        host_keys = jax.device_get(keys)
        out = self.orig(params, opt_state, batch, keys)
        self.steps.append({"batch": host_batch, "keys": host_keys,
                           "loss": float(out[2]["loss"]),
                           "device_batch": batch if i == 0 else None})
        if i == 0:
            self.mu1 = jax.device_get(out[1].mu)
        if i == self.n - 1:
            self.params_n = jax.device_get(out[0])
        return out

    def detach(self):
        self.trainer.step = self.orig
        for s in self.steps:
            s.pop("device_batch", None)


def edges_per_epoch(trainer) -> int:
    """Real positive edges one epoch trains on: every core edge of every
    partition the epoch's batches cover (the pipeline zips partitions and
    stops at the shortest stream)."""
    cores = [p.num_core_edges for p in trainer.pre.partitions]
    if trainer._fullgraph:
        return int(sum(cores))
    b = trainer.cfg.batch_size
    steps = min(-(-c // b) for c in cores)
    return int(sum(min(c, steps * b) for c in cores))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    traffic = ctx.traffic
    data, trainer, host_prep_s = build_trainer(ctx)
    params0 = start(ctx, trainer, data)
    rec = StepRecorder(trainer, traffic["checked_steps"])
    while len(rec.steps) < rec.n:
        trainer.train_epoch()
    rec.detach()
    per_epoch = edges_per_epoch(trainer)
    setup_s = time.perf_counter() - ctx.t_start

    probe = (harness.StepProbe(trainer, rec.steps[-1]["batch"])
             if ctx.trace else None)
    session = trace.Session(ctx.chips, ctx.kernels) if ctx.trace else None
    epochs = []
    watch, gcw = harness.CompileWatch(), harness.GcWatch()
    with session or contextlib.nullcontext(), watch, gcw:
        t0 = time.perf_counter()
        with ctx.span("bench.window"):
            while True:
                epochs.append(trainer.train_epoch())
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
        window_s = time.perf_counter() - t0
    memory = harness.peak_memory(jax.devices()[:ctx.chips])
    steps = sum(e["num_batches"] for e in epochs)
    per = np.array([e["t_device_step"] / max(e["num_batches"], 1)
                    for e in epochs])
    print(f"train: {len(epochs)} epochs, {steps} steps in {window_s:.3f} s; "
          f"dispatch+wait per step min {per.min():.4f} median "
          f"{np.median(per):.4f} max {per.max():.4f} s; "
          f"in the window {watch.report()}; garbage collector "
          f"{gcw.report()}; device memory "
          f"{harness.memory_stats(jax.devices()[0])}", file=sys.stderr)
    counters = {
        "window_s": window_s, "host_prep_s": host_prep_s, "steps": steps,
        "input_wait_s": sum(e["t_warmup"] + e["t_get_compute_graph"]
                            for e in epochs),
    }
    reduced = None
    if probe is not None:
        counters["train"] = probe.counts(ctx.cfg)
        probe.detach()
        # the step's instructions by named scope, from the step compiled
        # for the shapes the window ran (after the window and the memory
        # read, so that neither moves)
        t1 = time.perf_counter()
        module, by_instruction = scopes.instruction_scopes(
            trainer.lower_step(probe.last_batch).compile().as_text())
        print(f"train: step module {module}, {len(by_instruction)} "
              f"instructions, lowered and compiled in "
              f"{time.perf_counter() - t1:.3f} s", file=sys.stderr)
        reduced = session.reduce(module, by_instruction)

    # free the program's state before the reference runs on the chip
    trainer.close()
    del trainer, probe
    gc.collect()
    ref = ctx.reference
    common = dict(mode="full" if ctx.cfg["batch_size"] is None
                  else "minibatch",
                  num_negatives=ctx.cfg["num_negatives"],
                  dropout=ctx.cfg["dropout"], lr=ctx.cfg["learning_rate"],
                  adam=ctx.cfg["adam"])
    out = ref.replay(params0, data["features"], rec.steps, **common,
                     dtype=jnp.float32)
    prog = {"losses": [s["loss"] for s in rec.steps],
            "grad1": jax.tree_util.tree_map(
                lambda m: np.asarray(m) / (1 - ctx.cfg["adam"]["b1"]),
                rec.mu1),
            "params": rec.params_n}
    readings = compare(prog, out, params0)
    checks = {k: {"value": v, "limit": ctx.limits.get(k)}
              for k, v in readings.items()}
    return {"setup_s": setup_s,
            "end_to_end": {
                "train_edges_per_s": per_epoch * len(epochs) / window_s},
            "attempted": steps, "failed": 0,
            "memory_peak_bytes": memory, "counters": counters,
            "trace": reduced, "checks": checks, "readings": readings}


def _leaves(tree):
    import jax
    return [(jax.tree_util.keystr(p), np.asarray(x, np.float64))
            for p, x in jax.tree_util.tree_leaves_with_path(tree)]


def compare(prog: dict, ref: dict, params0) -> dict:
    """The numbers compared with the reference.

    * ``loss_gap``: the largest relative gap of a step's loss.
    * ``grad_gap``: of the first step's gradient as the optimizer got it
      (Adam's first moment after one step over 1 - b1), the worst leaf's
      gap between the program's norm and the reference's, over the larger
      of that leaf's reference norm and the median leaf's.
    * ``delta_gap``: the same for the parameters' change over the checked
      steps.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of both: Adam moves them by round-off alone.
    """
    losses_p, losses_r = prog["losses"], ref["losses"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses_p, losses_r))
    g_ref = _leaves(ref["grad1"])
    g_prog = dict(_leaves(prog["grad1"]))
    norms = {k: float(np.linalg.norm(v)) for k, v in g_ref}
    med = float(np.median(list(norms.values())))
    keep = [k for k, n in norms.items() if n >= 1e-3 * med]
    grad_gap = max(
        abs(float(np.linalg.norm(g_prog[k])) - norms[k]) / max(norms[k], med)
        for k in keep)
    p0 = dict(_leaves(params0))
    d_ref = {k: float(np.linalg.norm(v - p0[k]))
             for k, v in _leaves(ref["params"])}
    d_prog = {k: float(np.linalg.norm(v - p0[k]))
              for k, v in _leaves(prog["params"])}
    d_med = float(np.median([d_ref[k] for k in keep]))
    delta_gap = max(abs(d_prog[k] - d_ref[k]) / max(d_ref[k], d_med)
                    for k in keep)
    return {"loss_gap": float(loss_gap), "grad_gap": float(grad_gap),
            "delta_gap": float(delta_gap)}
