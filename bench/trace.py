"""Record a profiler trace of the measured window and reduce it to numbers.

``Session`` records the window with ``jax.profiler`` into a temporary
directory (under ``TMPDIR``) and ``reduce`` reads the ``.xplane.pb`` back
with ``jax.profiler.ProfileData``.  The window is the host span
``bench.window`` that the driver opens around it.  From the device planes
(``/device:TPU:<n>``, their ``XLA Ops`` line) it computes, for the chips
the cell uses:

* ``busy_s``: the union of the intervals in which an operation ran on a
  chip, inside the window, averaged over the chips; ``window_s`` the
  window's length; the idle share is 1 - busy_s / window_s;
* ``op_s``: device seconds by operation (summed over the chips and
  divided by their number), named ``name type kind`` from the HLO text the
  trace gives; ``kernel_s`` the same for the operations whose HLO text
  matches a kernel's pattern;
* ``collective_s`` and ``collective_exposed_s``: time in collective
  operations, and the part of it with no other operation running on that
  chip;
* ``breakdown``: the ten operations that took most device time, and the
  idle time of the window by what the host was doing, named by the
  innermost benchmark span open on the thread that owns the window (gaps
  with no span open are named ``host``).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "train.", "serve.", "pipeline.", "step.", "gen.")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|psum")


class Session:
    """``with Session(): ...`` records a trace; ``reduce()`` reads it."""

    def __init__(self, chips: int, kernels: Optional[Dict[str, str]] = None):
        self.chips = chips
        self.kernels = kernels or {}
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def reduce(self) -> dict:
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            from jax.profiler import ProfileData
            return reduce(ProfileData.from_file(paths[0]), self.chips,
                          self.kernels)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------- #
# interval arithmetic (nanoseconds)
# ---------------------------------------------------------------------- #
def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(base, cover) -> List[Tuple[int, int]]:
    """``base`` minus ``cover``; both sorted and disjoint."""
    out, j = [], 0
    for a, b in base:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ---------------------------------------------------------------------- #
# reduction
# ---------------------------------------------------------------------- #
def _events(line):
    for e in line.events:
        start = int(e.start_ns)
        yield e.name, start, start + int(e.duration_ns)


def short_name(text: str) -> str:
    """``name type kind`` of an HLO instruction's text as the TPU trace
    names its operation (``%fusion.3 = f32[8,75]{1,0:T(8,128)} fusion(...``
    becomes ``fusion.3 f32[8,75] fusion``); other names pass unchanged."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):
        depth, end = 0, 0
        for end, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[:end + 1], rest[end + 1:]
    else:
        typ, _, rest = rest.partition(" ")
    kind = re.match(r"\s*([\w\-]+)", rest)
    typ = re.sub(r"\{[^}]*\}", "", typ)
    return " ".join(x for x in (name.lstrip("%"), typ,
                                kind.group(1) if kind else "") if x)


def device_planes(profile, chips: int):
    planes = []
    for p in profile.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", p.name)
        if m and int(m.group(1)) < chips:
            planes.append(p)
    return planes


def host_spans(profile):
    """``(thread line, [(name, start, end)])`` of every benchmark span."""
    out = []
    for p in profile.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            spans = [ev for ev in _events(line)
                     if ev[0].startswith(SPAN_PREFIXES)]
            if spans:
                out.append((line.name, spans))
    return out


def label_segments(spans, lo: int, hi: int):
    """Cut ``[lo, hi)`` into segments, each named by the innermost span
    open over it (``host`` where none is).  Spans of one thread nest, so
    the innermost is the top of a stack swept in time order."""
    events = []
    for name, a, b in spans:
        if name == WINDOW_SPAN or b <= lo or a >= hi:
            continue
        events.append((max(a, lo), 1, -max(b, lo), name))
        events.append((min(b, hi), 0, 0, name))
    events.sort()
    out, stack, t = [], [], lo
    for when, is_start, _, name in events:
        if when > t:
            out.append((stack[-1] if stack else "host", t, when))
            t = when
        if is_start:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
    if t < hi:
        out.append((stack[-1] if stack else "host", t, hi))
    return out


def reduce(profile, chips: int, kernels: Optional[Dict[str, str]] = None
           ) -> dict:
    """Numbers of one recorded window (see the module's docstring).
    ``kernels`` maps a kernel's name to a regular expression matched
    against device operation names."""
    kernels = kernels or {}
    threads = host_spans(profile)
    window = None
    spans: List[Tuple[str, int, int]] = []
    for _, sp in threads:
        w = [s for s in sp if s[0] == WINDOW_SPAN]
        if w:
            window, spans = w[0], sp
            break
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window[1], window[2]
    window_ns = hi - lo

    planes = device_planes(profile, chips)
    busy, coll, coll_exposed = [], 0, 0
    op_ns: Dict[str, int] = {}
    kernel_ns: Dict[str, int] = {k: 0 for k in kernels}
    patterns = {k: re.compile(v) for k, v in kernels.items()}
    first_union = None
    for plane in planes:
        ops, colls = [], []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for name, a, b in _events(line):
                iv = clip([(a, b)], lo, hi)
                if not iv:
                    continue
                short = short_name(name)
                op_ns[short] = op_ns.get(short, 0) + length(iv)
                for kname, rx in patterns.items():
                    if rx.search(name):
                        kernel_ns[kname] += length(iv)
                (colls if COLLECTIVE.search(name) else ops).extend(iv)
        u = union(ops + colls)
        if first_union is None:
            first_union = u
        busy.append(length(u))
        cu = union(colls)
        coll += length(cu)
        coll_exposed += length(subtract(cu, union(ops)))
    n = max(len(planes), 1)
    op_s = {k: v / n / 1e9 for k, v in op_ns.items()}
    kernel_s = {k: v / n / 1e9 for k, v in kernel_ns.items()}

    # idle time of the first chip, named by the innermost open span
    gaps = subtract([(lo, hi)], first_union or [])
    idle: Dict[str, int] = {}
    segments = label_segments(spans, lo, hi)
    j = 0
    for name, a, b in segments:          # both lists sorted: one merge
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < b:
            overlap = min(b, gaps[k][1]) - max(a, gaps[k][0])
            idle[name] = idle.get(name, 0) + max(overlap, 0)
            k += 1
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": (sum(busy) / n) / 1e9,
        "device_planes": len(planes),
        "op_s": op_s,
        "kernel_s": kernel_s,
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": coll_exposed / n / 1e9,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in top_idle],
        },
    }
