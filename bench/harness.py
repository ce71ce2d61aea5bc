"""Helpers the drivers share: the error that ends a run without a result,
keys from a seed of any size, the peak device memory, and the
pass-through probe that a traced run (``--trace 1``) installs around the
training step.

A probe counts what the call was given; it changes no argument and no
result and opens no span (the program opens its own).  Runs with
``--trace 0`` install none, so the timed path is the program's own.
"""
from __future__ import annotations

import numpy as np


class Error(Exception):
    """A run that cannot produce a result: exit non-zero, print none."""


def seed_keys(seed: int):
    """Two PRNG keys from a seed of any size: the weights', and the one the
    program's own randomness (negatives, dropout) is drawn from."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.random.split(key)


def memory_stats(device) -> dict:
    try:
        return dict(device.memory_stats() or {})
    except Exception:              # a backend that keeps no statistics
        return {}


def peak_memory(devices) -> int:
    """The peak device memory of the fullest of ``devices``: the peak of
    the buffers in use plus the peak the runtime reserved for the
    programs' temporaries (the TPU keeps a step's scratch there, outside
    ``peak_bytes_in_use``)."""
    peaks = [0]
    for d in devices:
        stats = memory_stats(d)
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


class StepProbe:
    """Training: the real vertex, edge and triplet counts of every step
    (the full-graph batch is resident, so its counts are read once from
    the host copy; a streamed batch's are summed on the device as it is
    handed to the step), and the last batch the step was given (its
    buffers may have been donated to the step; its shapes, types and
    placements are what a lowering of the step needs)."""

    KEYS = ("vertex_mask", "comp_mask", "triplet_mask")

    def __init__(self, trainer, example_batch):
        import jax
        import jax.numpy as jnp
        self.trainer = trainer
        self.orig_step = trainer.step
        self.sums = []
        self.last_batch = None
        self.full = trainer._fullgraph
        if not self.full:
            # compiled here, in set-up, so that the window compiles nothing
            self._count = jax.jit(lambda b: [jnp.sum(b[k], axis=1)
                                             for k in self.KEYS])
            self._count({k: example_batch[k] for k in self.KEYS})
        trainer.step = self._step

    def _step(self, params, opt_state, batch, keys):
        if self.full:
            self.sums.append(None)
        else:
            self.sums.append(self._count({k: batch[k] for k in self.KEYS}))
        self.last_batch = batch
        return self.orig_step(params, opt_state, batch, keys)

    def step_counts(self):
        """``(vertices, edges, triplets)`` per trainer, for each step."""
        if self.full:
            pad = self.trainer.pre.padded
            s = self.trainer.cfg.num_negatives
            one = list(zip(pad.vertex_mask.sum(1), pad.edge_mask.sum(1),
                           pad.core_edge_mask.sum(1) * (1 + s)))
            return [one] * len(self.sums)
        return [list(zip(*(np.asarray(x) for x in s))) for s in self.sums]

    def counts(self, cfg) -> dict:
        """What ``bench/counts/rgcn.py`` counts the window's work from: the
        model's sizes and each step's real counts per trainer."""
        return {"d_in": cfg["dataset"].get("feature_dim") or cfg["hidden_dim"],
                "hidden": cfg["hidden_dim"], "bases": cfg["num_bases"],
                "layers": cfg["num_hops"],
                "steps": [[[int(v), int(e), int(t)] for v, e, t in step]
                          for step in self.step_counts()]}

    def detach(self):
        self.trainer.step = self.orig_step


class GcWatch:
    """The garbage collector's pauses while it is open: how many of each
    generation, and the longest (a long pause stalls a single-threaded
    loop, and shows in its latency tail)."""

    def __init__(self):
        self.pauses = []            # (generation, seconds)
        self._t0 = None

    def _callback(self, phase, info):
        import time
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        import gc
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        import gc
        gc.callbacks.remove(self._callback)
        return False

    def report(self) -> str:
        if not self.pauses:
            return "no collections"
        gen, longest = max(self.pauses, key=lambda p: p[1])
        full = sum(1 for g, _ in self.pauses if g == 2)
        total = sum(t for _, t in self.pauses)
        return (f"{len(self.pauses)} collections ({full} full) took "
                f"{total:.3f} s, the longest {longest:.3f} s (generation "
                f"{gen})")


class CompileWatch:
    """Counts what JAX traces, lowers and compiles while it is open (the
    measured window should do none of it) and the seconds they took."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.seconds = {v: 0.0 for v in self.EVENTS.values()}
        self.longest = {v: 0.0 for v in self.EVENTS.values()}

    def _listen(self, event, secs, **_):
        kind = self.EVENTS.get(event)
        if kind:
            self.counts[kind] += 1
            self.seconds[kind] += secs
            self.longest[kind] = max(self.longest[kind], secs)

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False

    def report(self) -> str:
        return ", ".join(f"{self.counts[k]} {k} ({self.seconds[k]:.3f} s, "
                         f"the longest {self.longest[k]:.3f} s)"
                         for k in self.counts)
