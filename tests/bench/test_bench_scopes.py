"""``bench/scopes.py``: the step's device time by the program's named
scopes, on fake profiles whose operations are named as the TPU trace names
them (HLO text without metadata) and a compiled module's text that carries
the ``op_name`` metadata; and ``bench/layers.py`` rehearsed on the CPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest
from bench import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: f32[75,80]) -> f32[75,80] {
  %param_0.1 = f32[75,80]{0,1} parameter(0)
  %reshape.9 = f32[8,75]{1,0} reshape(%param_0.1), metadata={op_name="jit(step)/vmap(transpose(jvp(kge.decoder_loss)))/mul" stack_frame_id=82}
  ROOT %scatter.4 = f32[75,80]{0,1} scatter(%param_0.1, %reshape.9), to_apply=%region_1
}

%fused_computation.8 (param_0.2: f32[75,80]) -> f32[75,80] {
  %param_0.2 = f32[75,80]{0,1} parameter(0)
  ROOT %fusion.9 = f32[75,80]{0,1} fusion(%param_0.2), kind=kCustom, calls=%fused_computation.7
}

ENTRY %main.3 (p: f32[75,80]) -> f32[75,80] {
  %p = f32[75,80]{0,1} parameter(0), metadata={op_name="params[\\'w\\']"}
  %fusion.1 = f32[75,80]{0,1} fusion(%p), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/kge.message/kge.aggregate/add"}
  %fusion.2 = f32[75,80]{0,1} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/vmap(transpose(jvp(kge.message)))/dot_general"}
  %fusion.2.remat2 = f32[75,80]{0,1} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/vmap(jvp(kge.gather))/gather"}
  %fusion.3 = f32[75,80]{0,1} fusion(%fusion.2), kind=kCustom, calls=%fused_computation.8
  %sort.1 = (s32[64]{0}, s32[64]{0}) sort(%fusion.3, %fusion.3), dimensions={0}, to_apply=%compare.1
  %multiply.5 = f32[] multiply(%fusion.3, %fusion.3), metadata={op_name="jit(step)/div"}
  ROOT %fusion.4 = f32[75,80]{0,1} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/kge.optimizer/sub"}
}
"""


def test_instruction_scopes_innermost_backward_remat_and_fusion_roots():
    module, by = scopes.instruction_scopes(HLO)
    assert module == "jit_step"
    assert by["fusion.1"] == "kge.aggregate"            # innermost
    assert by["fusion.2"] == "kge.message"              # backward
    assert by["fusion.2.remat2"] == "kge.gather"        # a remat copy
    assert by["fusion.3"] == "kge.decoder_loss"         # from inside
    assert by["fusion.4"] == "kge.optimizer"
    assert by["sort.1"] == scopes.UNSCOPED              # no metadata
    assert by["multiply.5"] == scopes.UNSCOPED          # no kge scope
    assert by["p"] == scopes.UNSCOPED


def test_innermost_ignores_names_that_are_not_scopes():
    # every ``kge.<name>`` on the path is a scope, known or not
    assert scopes.innermost("jit(step)/kge.message/kge.other/add") == \
        "kge.other"
    # a name that only holds one is not
    assert scopes.innermost(
        "jit(step)/kge.message/pjit(kge_score)/xkge.other/kge.a.b/add") == \
        "kge.message"
    assert scopes.innermost("") == scopes.UNSCOPED


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


def _op(name, start, end):
    return _Event(f"%{name} = f32[75,80]{{0,1:T(8,128)}} fusion(%p)",
                  start * MS, end * MS)


def _plane(index, shift=0):
    modules = [_Event("jit_step(8784)", (10 + shift) * MS, (60 + shift) * MS),
               _Event("jit__threefry_split(93)", (60 + shift) * MS,
                      (70 + shift) * MS),
               _Event("jit_step(8784)", (100 + shift) * MS,
                      (150 + shift) * MS)]
    s = shift
    ops = [_op("fusion.1", 10 + s, 20 + s),        # kge.aggregate
           _op("fusion.2", 20 + s, 25 + s),        # kge.message
           _op("fusion.2.remat2", 25 + s, 27 + s),  # kge.gather
           _op("fusion.3", 27 + s, 40 + s),        # kge.decoder_loss
           _op("sort.1", 40 + s, 43 + s),          # unscoped
           _op("fusion.4", 43 + s, 44 + s),        # kge.optimizer
           _op("fusion.1", 61 + s, 69 + s),        # another module
           _op("fusion.2", 100 + s, 110 + s),      # second run
           _op("fusion.3", 140 + s, 160 + s)]      # crosses the window
    return _Plane(f"/device:TPU:{index}", [_Line("XLA Modules", modules),
                                           _Line("XLA Ops", ops)])


def test_attribute_counts_the_step_module_inside_the_window():
    profile = _Profile([_Plane("/host:CPU", []), _plane(0)])
    out = scopes.attribute(profile, 1, 0, 150 * MS, "jit_step",
                           scopes.instruction_scopes(HLO)[1])
    s = {k: round(v * 1e3, 6) for k, v in out["scope_s"].items()}
    assert s == {"kge.aggregate": 10, "kge.message": 15, "kge.gather": 2,
                 "kge.decoder_loss": 13 + 10, "kge.optimizer": 1,
                 "unscoped": 3}
    assert out["step_module_s"] == pytest.approx(
        sum(out["scope_s"].values()))
    assert out["step_module_s"] == pytest.approx(0.054)


def test_attribute_averages_over_the_chips_it_is_given():
    profile = _Profile([_plane(0), _plane(1, shift=1), _plane(2)])
    out = scopes.attribute(profile, 2, 0, 1000 * MS, "jit_step",
                           scopes.instruction_scopes(HLO)[1])
    # chip 2 is not the cell's; each of chips 0 and 1 ran 64 ms of the step
    assert out["step_module_s"] == pytest.approx(0.064)
    assert out["scope_s"]["kge.decoder_loss"] == pytest.approx(0.033)


def test_attribute_without_the_step_module_reads_zero():
    profile = _Profile([_plane(0)])
    out = scopes.attribute(profile, 1, 0, 1000 * MS, "jit_other",
                           scopes.instruction_scopes(HLO)[1])
    assert out["step_module_s"] == 0
    assert set(out["scope_s"]) == set(scopes.SCOPES) | {scopes.UNSCOPED}


def test_a_scope_the_known_set_lacks_is_read_as_its_own():
    """A configuration's own scope (a feature-input projection, say) is
    attributed to itself, not to ``unscoped``."""
    text = HLO.replace('op_name="jit(step)/vmap(jvp(kge.gather))/gather"',
                       'op_name="jit(step)/vmap(jvp(kge.input))/dot_general"')
    assert "kge.input" not in scopes.SCOPES
    _, by = scopes.instruction_scopes(text)
    assert by["fusion.2.remat2"] == "kge.input"
    out = scopes.attribute(_Profile([_plane(0)]), 1, 0, 150 * MS,
                           "jit_step", by)
    assert out["scope_s"]["kge.input"] == pytest.approx(0.002)
    assert out["scope_s"]["unscoped"] == pytest.approx(0.003)
    assert "kge.gather" not in out["scope_s"]


def _record(planes=1, steps=4):
    """A traced run's record as ``bench/run.py`` hands it to a reader."""
    scope_s = {"kge.gather": 0.004, "kge.message": 0.8,
               "kge.aggregate": 0.6, "kge.decoder_loss": 0.2,
               "kge.optimizer": 0.0002, "unscoped": 0.04}
    return {"platform": "tpu", "chips": 1, "peaks": {},
            "counters": {"steps": steps},
            "trace": {"device_planes": planes, "scope_s": scope_s,
                      "step_module_s": sum(scope_s.values())}}


READINGS = {"gather_ms.train": 1.0, "message_ms.train": 200.0,
            "aggregate_ms.train": 150.0, "decoder_loss_ms.train": 50.0,
            "optimizer_ms.train": 0.05,
            "unscoped_share.train": 100 * 0.04 / 1.6442}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_scope_reader_reads_a_synthetic_record(name):
    from bench import run
    reader = run.load_module("metrics", name)
    assert reader.read(_record()) == pytest.approx(READINGS[name])
    # no device plane (a CPU rehearsal), or no trace: nothing to read
    assert reader.read(_record(planes=0)) is None
    assert reader.read({**_record(), "trace": None}) is None


def test_the_scope_readers_add_up_to_the_step_module():
    from bench import run
    rec = _record()
    ms = sum(run.load_module("metrics", n).read(rec)
             for n in READINGS if n.endswith("_ms.train"))
    unscoped = run.load_module("metrics", "unscoped_share.train").read(rec)
    step_ms = 1000 * rec["trace"]["step_module_s"] / rec["counters"]["steps"]
    assert ms + unscoped / 100 * step_ms == pytest.approx(step_ms)


def test_a_compiled_step_puts_its_heavy_operations_under_scopes():
    from repro.data import synthetic_fb15k
    from repro.training import KGETrainer, TrainConfig
    tr = KGETrainer(synthetic_fb15k(scale=0.01, seed=0),
                    TrainConfig(num_trainers=2, epochs=1, hidden_dim=16,
                                batch_size=None))
    text = tr.lower_step().compile().as_text()
    module, by = scopes.instruction_scopes(text)
    assert module == "jit_step"
    assert set(by.values()) == set(scopes.SCOPES) | {scopes.UNSCOPED}
    heavy = [n for n in by if n.split(".")[0] in ("scatter", "gather",
                                                  "dot")]
    assert heavy and all(by[n] != scopes.UNSCOPED for n in heavy)


def test_layers_rehearsal_prints_one_line_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "layers.py"),
         "--workload", "fb15k237.train.full", "--seed", "4294967311",
         "--epochs", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["steps"] == 1 and line["device"]["platform"] == "cpu"
    # a CPU run reports no device time
    assert line["scope_ms_per_step"] is None
    assert {"partition", "expand", "pad", "init"} <= set(
        line["setup_stage_s"])
    assert line["host_prep_s"] >= line["setup_stage_s"]["partition"]
