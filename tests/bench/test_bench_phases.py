"""Device time by phase of the ZO step (``benchmarks/chip/phases.py``): on
hand-made records whose answers are known, and on two steps of a v5e trace
of the training cell recorded with the step's phase scopes."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip import flops, manifest, peaks
from benchmarks.chip import phases as ph
from benchmarks.chip import trace_reduce as tr
from benchmarks.chip.kinds import zo_train

DATA = Path(__file__).parent / "data"

HLO = """\
%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/zo_perturb/add"}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/zo_perturb/add" source_file="x.py" source_line=3}
  %while.4 = f32[8]{0} while(%fusion.3), condition=%cond, body=%body, metadata={op_name="jit(step)/zo_perturb/zo_forward/while"}
  %dot.5 = f32[8]{0} dot(%while.4, %p), metadata={op_name="jit(step)/zo_perturb/vmap(zo_forward)/dot_general"}
  %zo_affine_2d.6 = f32[8]{0} custom-call(%dot.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/zo_update/jit(zo_affine)/pallas_call"}
  %copy.7 = f32[8]{0} copy(%zo_affine_2d.6)
  ROOT %fusion.8 = f32[8]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jit(step_key)/threefry2x32"}
}
"""

T = "f32[8]{0} "
OPS = [["%fusion.3 = " + T + "fusion(f32[8]{0} %p)", 0, 10],
       ["%while.4 = " + T + "while(f32[8]{0} %fusion.3)", 10, 100],
       ["%dot.5 = " + T + "dot(f32[8]{0} %a)", 20, 30],       # in the while
       ["%dot.5 = " + T + "dot(f32[8]{0} %a)", 60, 30],       # in the while
       ["%zo_affine_2d.6 = " + T + "custom-call(f32[8]{0} %dot.5)", 110, 40],
       ["%copy.7 = " + T + "copy(f32[8]{0} %zo_affine_2d.6)", 150, 10],
       ["%fusion.8 = " + T + "fusion(f32[8]{0} %copy.7)", 160, 20],
       ["%zo_affine_2d.6 = " + T + "custom-call(f32[8]{0} %dot.5)", 190, 50]]
WINDOW = (0, 200)      # the last op runs past its end


@pytest.fixture(scope="module")
def op_names():
    return ph.hlo_op_names(HLO)


def test_op_names_from_the_compiled_text(op_names):
    assert op_names == {
        "add.1": "jit(step)/zo_perturb/add",
        "fusion.3": "jit(step)/zo_perturb/add",
        "while.4": "jit(step)/zo_perturb/zo_forward/while",
        "dot.5": "jit(step)/zo_perturb/vmap(zo_forward)/dot_general",
        "zo_affine_2d.6": "jit(step)/zo_update/jit(zo_affine)/pallas_call",
        "fusion.8": "jit(step)/jit(step_key)/threefry2x32"}
    assert ph.instruction(OPS[4][0]) == "zo_affine_2d.6"
    assert ph.instruction("fusion.3") == "fusion.3"


def test_each_op_takes_the_innermost_phase(op_names):
    assert ph.op_phases(OPS, op_names) == [
        ph.PERTURB, ph.FORWARD, ph.FORWARD, ph.FORWARD, ph.UPDATE,
        None, None, ph.UPDATE]


def test_phase_time_is_a_union_clipped_to_the_window(op_names):
    phase = ph.op_phases(OPS, op_names)
    # the while spans its body's dots: 100, not 100 + 30 + 30
    assert ph.phase_ns(OPS, phase, WINDOW, {ph.FORWARD}) == 100
    # the second kernel call counts 10 of its 50: the window ends at 200
    assert ph.phase_ns(OPS, phase, WINDOW, {ph.UPDATE}) == 40 + 10
    assert ph.phase_ns(OPS, phase, WINDOW, {ph.PERTURB, ph.UPDATE}) \
        == 10 + 50


def test_a_scope_takes_its_ops_from_the_phase_around_it(op_names):
    """``scope_ns_per_step``: a phase keeps the ops of a phase nested in
    it out, a new scope inside a phase takes its ops, and a run without
    op names or without an op under the scopes reads nothing."""
    run = {"op_names": op_names, "steps": 2,
           "trace": {"device_ops": OPS, "window": WINDOW}}
    assert ph.scope_ns_per_step(run, (ph.PERTURB,)) == 10 / 2
    assert ph.scope_ns_per_step(run, (ph.FORWARD,)) == 100 / 2
    names = dict(op_names, **{"dot.5": "jit(step)/zo_forward/moe/dot"})
    assert ph.scope_ns_per_step(dict(run, op_names=names), ("moe",)) \
        == (30 + 30) / 2
    assert ph.phase_of("jit(step)/zo_forward/moe/dot", ("moe",)) == "moe"
    assert ph.phase_of("jit(step)/zo_forward/moe/dot") == ph.FORWARD
    assert ph.scope_ns_per_step(dict(run, op_names={}), (ph.FORWARD,)) \
        is None
    assert ph.scope_ns_per_step({"job": "none"}, (ph.FORWARD,)) is None


def test_an_op_with_no_phase_counts_in_none(op_names):
    phase = ph.op_phases(OPS, op_names)
    named = ph.phase_ns(OPS, phase, WINDOW, set(ph.PHASES))
    assert tr.busy_ns(OPS, WINDOW) - named == 10 + 20   # copy.7, fusion.8
    assert ph.op_phases(OPS, {}) == [None] * len(OPS)
    assert ph.phase_ns(OPS, [None] * len(OPS), WINDOW, set(ph.PHASES)) == 0


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_zo_short_phases.json").read_text())


def test_recorded_trace_by_phase(recorded):
    """Two steps of opt-13b-8l.zo-short on one v5e: the perturbation and
    update passes and the forwards cover the busy time, and every Pallas
    kernel call falls in perturbation or update."""
    ops, win, want = (recorded["device_ops"], recorded["window"],
                      recorded["expect"])
    phase = ph.op_phases(ops, recorded["op_names"])
    busy = tr.busy_ns(ops, win)
    assert busy == want["busy_ns"]
    for name in ph.PHASES:
        assert ph.phase_ns(ops, phase, win, {name}) == want["phase_ns"][name]
    pu = ph.phase_ns(ops, phase, win, {ph.PERTURB, ph.UPDATE})
    fw = ph.phase_ns(ops, phase, win, {ph.FORWARD})
    assert pu == want["perturb_update_ns"]
    assert (pu + fw) / busy >= 0.97
    assert pu + fw <= busy
    kernels = [p for o, p in zip(ops, phase)
               if any(n in o[0] for n in zo_train.KERNEL_NEEDLES)]
    assert kernels and set(kernels) <= {ph.PERTURB, ph.UPDATE}


@pytest.mark.parametrize("name,want", [
    ("perturb_ms.train", 997757317 / 2 / 1e6),
    ("perturb_roofline.train",
     100 * 36_382_433_280 / 819e9 / (997757317 / 2 / 1e9)),
    ("forward_ms.train", 270414988 / 2 / 1e6),
    ("forward_mfu.train",
     100 * 22_811_728_936_960 / (270414988 / 2 / 1e9) / 197e12)])
def test_phase_readers_on_the_recorded_trace(recorded, name, want):
    """The readers of ``BENCHMARK.json``'s phase metrics, on the recorded
    two steps of zo-short (before the strip walk of the kernels) with the
    run's context as ``kinds/zo_train.py`` hands it over."""
    model = manifest.config("opt-13b-8l")["model"]
    run = {"job": "zo_train", "steps": 2, "op_names": recorded["op_names"],
           "trace": {"device_ops": recorded["device_ops"],
                     "window": recorded["window"]},
           "flops_per_step": 2 * flops.forward_flops(model, 16, 128),
           "kernel_bytes_per_step": flops.zo_kernel_bytes_per_step(model),
           "peaks": peaks.PEAKS["TPU v5 lite"]}
    read = manifest.reader(name)
    assert read(run) == pytest.approx(want, rel=1e-12)
    assert read(dict(run, op_names={})) is None
    assert read({"job": "none"}) is None
