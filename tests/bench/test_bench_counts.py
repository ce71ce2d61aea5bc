"""``bench/counts`` against hand counts at small shapes."""
from __future__ import annotations

import sys
import types

import pytest
from bench import counts
from bench import run as bench_run
from bench.counts import rgcn


def test_rgcn_forward_by_hand():
    # one layer, 3 vertices, 4 edges, 2 triplets, d_in = d = 2, 1 basis:
    # basis projection 2*1*3*2*2 = 24, self loop 2*3*2*2 = 24,
    # edge mix 2*1*4*2 = 16, aggregation 4*2 = 8, DistMult 3*2*2 = 12
    assert rgcn.forward_flops(3, 4, 2, 2, 2, 1, 1) == 24 + 24 + 16 + 8 + 12


def test_rgcn_two_layers_feature_input_and_backward():
    v, e, t, f, d, b = 10, 30, 8, 6, 4, 2
    first = 2 * b * v * f * d + 2 * v * f * d + 2 * b * e * d + e * d
    second = 2 * b * v * d * d + 2 * v * d * d + 2 * b * e * d + e * d
    fwd = first + second + 3 * t * d
    assert rgcn.forward_flops(v, e, t, f, d, b, 2) == fwd
    assert rgcn.train_step_flops(v, e, t, f, d, b, 2) == 3 * fwd


def test_rgcn_counts_scale_with_real_rows_only():
    base = rgcn.forward_flops(100, 1000, 50, 8, 8, 2, 2)
    assert rgcn.forward_flops(100, 2000, 50, 8, 8, 2, 2) > base
    assert rgcn.forward_flops(0, 0, 0, 8, 8, 2, 2) == 0


def test_rgcn_window_sums_every_trainer_of_every_step():
    train = {"d_in": 4, "hidden": 4, "bases": 2, "layers": 2,
             "steps": [[[10, 30, 8], [5, 12, 3]], [[10, 30, 8], [0, 0, 0]]]}
    one = rgcn.train_step_flops(10, 30, 8, 4, 4, 2, 2)
    other = rgcn.train_step_flops(5, 12, 3, 4, 4, 2, 2)
    assert rgcn.window_flops(train) == 2 * one + other


@pytest.fixture
def kernel(monkeypatch):
    """A kernel's counting module, ``bench/counts/scale_add.py`` as a later
    PR would add it: one operation and 12 bytes per element."""
    mod = types.ModuleType("bench.counts.scale_add")
    mod.PATTERN = r"custom-call\(f32\[\d+\]"
    mod.count = lambda c: {"flops": float(c["elements"]),
                           "bytes": 12.0 * c["elements"]}
    monkeypatch.setitem(sys.modules, "bench.counts.scale_add", mod)
    return mod


def test_a_kernel_metric_hands_its_pattern_to_the_trace(kernel):
    reader = types.SimpleNamespace(KERNEL="scale_add")
    other = types.SimpleNamespace()           # a metric of no kernel
    assert bench_run.kernel_patterns([reader, other]) == {
        "scale_add": kernel.PATTERN}
    assert bench_run.kernel_patterns([other]) == {}


def test_roofline_reads_counts_against_kernel_time(kernel):
    rec = {"peaks": {"flops_bf16_per_s": 1e12, "hbm_bytes_per_s": 1e9},
           "counters": {"elements": 10},
           "trace": {"kernel_s": {"scale_add": 1e-6}}}
    # 120 bytes at 1e9 B/s = 1.2e-7 s (its 10 operations take 1e-11 s)
    # against 1e-6 s measured
    assert counts.roofline(rec, "scale_add") == pytest.approx(12.0)
    # a kernel the trace did not find reads nothing, never 0
    missing = {**rec, "trace": {"kernel_s": {"scale_add": 0.0}}}
    assert counts.roofline(missing, "scale_add") is None
    assert counts.roofline({**rec, "peaks": None}, "scale_add") is None
