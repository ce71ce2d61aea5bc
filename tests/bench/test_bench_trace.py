"""``bench/trace.py`` on a small trace recorded on the CPU: host spans of
known length around a tiny jitted program, with device operations placed
inside those spans (the CPU has no device plane of its own, so one is
added to the recorded profile).  Idle share, per-name operation time,
collective time and the breakdown's gap attribution read as constructed."""
from __future__ import annotations

import time

import pytest
from bench import trace

MS = 1_000_000


class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


@pytest.fixture(scope="module")
def recorded():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.Session(chips=1) as session:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("serve.step"):
                f(x).block_until_ready()
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("gen.sleep"):
                time.sleep(0.05)
    import glob
    import os
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(session.dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    profile = ProfileData.from_file(path)
    spans = {n: (a, b) for _, sp in trace.host_spans(profile)
             for n, a, b in sp}
    yield profile, spans
    import shutil
    shutil.rmtree(session.dir, ignore_errors=True)


def test_host_spans_are_read_from_the_recorded_trace(recorded):
    _, spans = recorded
    assert {"bench.window", "serve.step", "gen.sleep"} <= set(spans)
    w0, w1 = spans["bench.window"]
    assert w1 - w0 >= 100 * MS
    s0, s1 = spans["serve.step"]
    assert w0 <= s0 < s1 <= w1 and s1 - s0 >= 50 * MS


def test_reduction_reads_as_constructed(recorded):
    profile, spans = recorded
    w0, w1 = spans["bench.window"]
    s0, s1 = spans["serve.step"]
    g0, g1 = spans["gen.sleep"]
    ops = [
        _Event("fusion.1", s0 + 5 * MS, s0 + 15 * MS),          # 10 ms
        _Event("kge_score_kernel", g0 + 10 * MS, g0 + 20 * MS),  # 10 ms
        _Event("all-reduce.3", g0 + 15 * MS, g0 + 30 * MS),      # 15 ms
        _Event("fusion.1", w0 - 20 * MS, w0 - 10 * MS),          # outside
    ]
    tpu = _Plane("/device:TPU:0", [_Line("XLA Ops", ops),
                                   _Line("XLA Modules", [])])
    out = trace.reduce(_Profile(list(profile.planes) + [tpu]), chips=1,
                       kernels={"kge_score": "kge_score"})
    window = (w1 - w0) / 1e9
    assert out["window_s"] == pytest.approx(window)
    busy = (10 + 20) * MS / 1e9            # fusion, then kernel + collective
    assert out["busy_s"] == pytest.approx(busy)
    assert out["device_planes"] == 1
    assert out["op_s"]["fusion.1"] == pytest.approx(0.010)
    assert out["kernel_s"]["kge_score"] == pytest.approx(0.010)
    assert out["collective_s"] == pytest.approx(0.015)
    assert out["collective_exposed_s"] == pytest.approx(0.010)
    idle = dict(out["breakdown"]["idle_gaps"])
    assert idle["serve.step"] == pytest.approx((s1 - s0 - 10 * MS) / 1e9)
    assert idle["gen.sleep"] == pytest.approx((g1 - g0 - 20 * MS) / 1e9)
    assert sum(idle.values()) == pytest.approx(window - busy)
    top = out["breakdown"]["device_ops"]
    assert top[0][0] == "all-reduce.3" and len(top) == 3


TODAY = {"window_s", "busy_s", "device_planes", "op_s", "kernel_s",
         "collective_s", "collective_exposed_s", "breakdown"}


def _step_plane(spans, module="jit_step(41)"):
    """A device plane with one run of the step module inside the window:
    ``fusion.1`` (8 ms) under ``kge.message``, ``sort.2`` (2 ms) under no
    scope."""
    s0, _ = spans["serve.step"]
    ops = [_Event("%fusion.1 = f32[8,75]{1,0} fusion(%p)", s0 + 1 * MS,
                  s0 + 9 * MS),
           _Event("%sort.2 = s32[64]{0} sort(%a)", s0 + 9 * MS,
                  s0 + 11 * MS)]
    return _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Event(module, s0, s0 + 12 * MS)]),
        _Line("XLA Ops", ops)])


def test_reduce_returns_every_key_it_did_and_the_scopes_when_given(
        recorded):
    profile, spans = recorded
    prof = _Profile(list(profile.planes) + [_step_plane(spans)])
    plain = trace.reduce(prof, chips=1)
    assert set(plain) == TODAY
    by = {"fusion.1": "kge.message", "sort.2": "unscoped"}
    out = trace.reduce(prof, chips=1, module="jit_step", scopes=by)
    assert set(out) == TODAY | {"scope_s", "step_module_s"}
    for k in TODAY:
        assert out[k] == plain[k], k
    assert out["scope_s"] == pytest.approx({"kge.message": 0.008,
                                            "unscoped": 0.002})
    assert out["step_module_s"] == pytest.approx(0.010)


def test_a_step_module_missing_from_the_device_planes_is_an_error(
        recorded):
    profile, spans = recorded
    prof = _Profile(list(profile.planes)
                    + [_step_plane(spans, module="jit_other(41)")])
    with pytest.raises(RuntimeError, match="jit_step"):
        trace.reduce(prof, chips=1, module="jit_step",
                     scopes={"fusion.1": "kge.message"})
    # with no device plane (a CPU run) there is nothing to check
    out = trace.reduce(profile, chips=1, module="jit_step", scopes={})
    assert out["step_module_s"] == 0


def test_no_window_span_is_an_error(recorded):
    profile, _ = recorded
    planes = [p for p in profile.planes if not p.name.startswith("/host:")]
    with pytest.raises(RuntimeError):
        trace.reduce(_Profile(planes), chips=1)


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.length(trace.clip([(0, 10), (20, 30)], 5, 25)) == 10


def test_short_names_of_hlo_text():
    assert trace.short_name(
        "%fusion.3 = f32[8,75]{1,0:T(8,128)} fusion(f32[8,75]{1,0} %p), "
        "kind=kLoop") == "fusion.3 f32[8,75] fusion"
    assert trace.short_name(
        "%branch_0_fun.4 = (f32[128,10]{1,0:T(8,128)S(1)}, s32[128,10]"
        "{1,0:T(8,128)S(1)}) custom-call(f32[128,16384]{1,0} %pad.30)"
    ) == "branch_0_fun.4 (f32[128,10], s32[128,10]) custom-call"
    assert trace.short_name("jit_step(123)") == "jit_step(123)"
