"""Run one benchmark cell on the accelerator and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, from ``BENCHMARK.json`` at the
root of the checkout:

* the cell's entry gives its configuration, traffic and chips;
* ``bench/configs/<config>.json`` holds the model's sizes, its dataset and
  the name of its plain reference, ``bench/reference/<reference>.py``;
* ``bench/traffic/<traffic>.json`` names the driver,
  ``bench/drivers/<driver>.py``, and holds the traffic's parameters;
* ``bench/workloads/<cell>.json`` holds the limits of the numbers that
  decide ``correct``;
* each per-layer metric is read by ``bench/metrics/<metric>.py``; one
  that names a ``KERNEL`` has its counts and trace pattern in
  ``bench/counts/<kernel>.py``.

A run loads, warms up every shape it will use (all counted in
``setup_s``), measures for ``--seconds``, reads the peak device memory,
frees the program's state, and then compares what the timed path produced
with the plain reference.  ``--trace 1`` records a profiler trace of the
window and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result; the numbers compared, each
beside its limit, are the last lines of standard error and the last key of
the result.  Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result, unless ``--rehearse`` shrinks
the cell for a CPU rehearsal, whose result names the CPU platform.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# the compile cache: a fixed directory of the checkout (its path is part of
# what a later run must find, so it never moves)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

from bench.harness import Error  # noqa: E402


def load_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise Error(f"missing {os.path.relpath(path, ROOT)}") from None


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise Error(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(benchmark: dict, name: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == name:
            return w
    raise Error(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(benchmark: dict, cell: str, kind: str) -> list:
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``):
    those without a ``workloads`` key and those that list the cell."""
    return [m for m in benchmark[kind]
            if cell in m.get("workloads", [cell])]


def apply_rehearsal(cfg: dict) -> dict:
    """The configuration with its ``rehearse`` overrides (dotted keys
    reach into nested groups)."""
    cfg = json.loads(json.dumps(cfg))
    for key, value in cfg.get("rehearse", {}).items():
        node, *path = key.split(".")
        if path:
            cfg[node][path[0]] = value
        else:
            cfg[node] = value
    return cfg


class Context:
    """What a driver gets: the seed, window and chips, the cell's
    configuration, traffic and limits, its reference module, and a host
    span helper."""

    def __init__(self, args, spec, cfg, traffic, limits):
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.chips = int(spec["chips"])
        self.cfg = cfg
        self.traffic = traffic
        self.limits = limits
        self.kernels = {}       # kernel name -> pattern, for the trace
        self.t_start = T_START
        self.reference = load_module("reference", cfg["reference"])
        # every program of the run is traced at the matmul precision the
        # configuration states (JAX's own default where it states none)
        precision = cfg.get("matmul_precision", "default")
        if precision != "default":
            import jax
            jax.config.update("jax_default_matmul_precision", precision)

    def span(self, name: str):
        """A host span on the profiler's clock (cheap when no trace is
        being recorded)."""
        import jax
        return jax.profiler.TraceAnnotation(name)


def kernel_patterns(readers) -> dict:
    """``{kernel: pattern}`` for the trace, from the per-layer metric
    readers that name a ``KERNEL`` (``bench/counts/<kernel>.py``)."""
    from bench import counts
    return {r.KERNEL: counts.kernel(r.KERNEL).PATTERN
            for r in readers if hasattr(r, "KERNEL")}


def judge(checks: dict) -> bool:
    """Every number compared lies within its limit; a number without a
    limit fails."""
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="shrink the cell and run on whatever platform JAX "
                         "finds (the CPU here); the result names it")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = run(args)
    except Error as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    checks = line["checks"]
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def run(args) -> dict:
    benchmark = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell_spec(benchmark, args.workload)
    cfg = load_json(os.path.join(BENCH, "configs", f"{spec['config']}.json"))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     f"{spec['traffic']}.json"))
    limits = load_json(os.path.join(BENCH, "workloads",
                                    f"{args.workload}.json"))["limits"]
    chips = int(spec["chips"])
    if args.rehearse:
        cfg = apply_rehearsal(cfg)
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    driver = load_module("drivers", traffic["driver"])

    jax = start_jax(args.rehearse)
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse:
        if platform != "tpu":
            raise Error(f"JAX finds no TPU (only {platform} devices); "
                        "--rehearse runs a shrunken CPU rehearsal")
        if len(devices) < chips:
            raise Error(f"the cell needs {chips} chips, JAX finds "
                        f"{len(devices)}")

    peaks = load_peaks(devices[0].device_kind, platform)
    ctx = Context(args, spec, cfg, traffic, limits)
    readers = [(m, load_module("metrics", m["name"]))
               for m in metrics_of(benchmark, args.workload, "per_layer")
               ] if args.trace else []
    ctx.kernels = kernel_patterns(r for _, r in readers)
    res = driver.run(ctx)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": judge(res["checks"]) and res["failed"] == 0,
            "attempted": res["attempted"], "failed": res["failed"]}
    if not args.trace:
        values = {"setup_s": res["setup_s"], **res["end_to_end"]}
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(benchmark, args.workload, "end_to_end")}
    else:
        trace = res["trace"]
        rec = {"platform": platform, "kind": devices[0].device_kind,
               "chips": chips, "counters": res["counters"],
               "trace": trace, "peaks": peaks}
        line["metrics"] = {}
        for m, reader in readers:
            value = reader.read(rec)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    line["device"] = device
    line["checks"] = res["checks"]
    return line


def start_jax(rehearse: bool):
    """JAX, with the program on the path and the program's own persistent
    compile cache on, as its entry points turn it on
    (``repro.launch.compile_cache``, JAX's default thresholds), in the
    directory the benchmark gives it: ``.jax_cache/`` in the checkout,
    whatever the environment named, so that what one checkout cached is
    never read by another.  A rehearsal keeps no cache."""
    if not rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    if not rehearse:
        from repro.launch.compile_cache import enable_compile_cache
        jax.config.update("jax_compilation_cache_dir",
                          enable_compile_cache())
    return jax


def load_peaks(kind: str, platform: str):
    """The chip's peaks; ``None`` off the TPU (a rehearsal), where no
    device metric is reported.  A TPU kind that is not in the table is an
    error, never a default."""
    if platform != "tpu":
        return None
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["chips"]:
        raise Error(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["chips"][kind]


if __name__ == "__main__":
    sys.exit(main())
