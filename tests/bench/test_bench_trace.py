"""The reduction from a profiler trace to busy time, kernel time, top ops
and attributed idle gaps: on hand-made records whose answers are known, and
on a small trace recorded from one TPU v5e run of the training cell."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip import trace_reduce as tr
from benchmarks.chip.kinds import zo_train

DATA = Path(__file__).parent / "data"

K = "bf16[256,512]{1,0} custom-call(bf16[256,512]{1,0} %p, s32[1] %s)"
OPS = [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 0, 100],
       ["%zo_affine_2d.7 = " + K, 50, 100],         # overlaps fusion.1
       ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)", 300, 50],
       ["%zo_affine_2d.9 = " + K, 400, 200],        # runs past the window
       ["%copy.3 = f32[8]{0} copy(f32[8]{0} %c)", 700, 10]]  # after it
SPANS = [["window", 0, 500], ["step_dispatch", 140, 200],
         ["ledger_fetch", 360, 30]]
WINDOW = (0, 500)


def test_busy_is_the_union_of_op_intervals():
    assert tr.busy_intervals(OPS, WINDOW) == [[0, 150], [300, 350],
                                              [400, 500]]
    assert tr.busy_ns(OPS, WINDOW) == 150 + 50 + 100
    assert tr.idle_share(OPS, WINDOW) == pytest.approx(1 - 300 / 500)


def test_kernel_time_by_name_inside_the_window():
    ops = tr.matching(OPS, ("zo_affine",))
    assert [o[0] for o in ops] == [OPS[1][0], OPS[3][0]]
    assert tr.op_ns(ops, WINDOW) == 100 + 100


def test_top_ops_add_up_one_kernels_calls():
    top = dict(tr.top_ops(OPS, WINDOW))
    assert top["%zo_affine_2d bf16[256,512]{1,0}"] == pytest.approx(200e-9)
    assert top["%fusion f32[8]{0}"] == pytest.approx(150e-9)
    assert not any(k.startswith("%copy") for k in top)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    gaps = tr.idle_gaps(OPS, SPANS, WINDOW)
    assert gaps == [["step_dispatch", pytest.approx(150e-9)],
                    ["ledger_fetch", pytest.approx(50e-9)]]


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace_zo_short.json"
    return json.loads(path.read_text())


def test_recorded_trace_reduces(recorded):
    """A trimmed window of two ZO steps of opt-13b-8l.zo-short on one v5e:
    the device is busy most of it, and the Pallas kernels are found under
    the name the chip's trace gives them."""
    ops, spans, win = (recorded["device_ops"], recorded["host_spans"],
                       recorded["window"])
    busy = tr.busy_ns(ops, win)
    assert 0 < busy <= win[1] - win[0]
    assert busy == recorded["expect"]["busy_ns"]
    kernel = tr.op_ns(tr.matching(ops, zo_train.KERNEL_NEEDLES), win)
    assert kernel == recorded["expect"]["kernel_ns"]
    assert 0 < kernel < busy
    gaps = tr.idle_gaps(ops, spans, win)
    assert gaps and all(name in zo_train.SPAN_NAMES + ("window", "idle")
                        for name, _ in gaps)
