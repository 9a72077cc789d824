"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (three ``bench.step`` annotations, each around a
paged-decode kernel call and a 1024 x 1024 matmul)."""

import os

import numpy as np
import pytest

import kernels
import trace_reduce as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small_trace.xplane.pb")


def test_merged_and_clip():
    iv = np.asarray([[5, 9], [0, 2], [1, 3], [8, 12], [20, 21]], np.int64)
    assert TR.merged(iv).tolist() == [[0, 3], [5, 12], [20, 21]]
    assert TR.clip(iv, 2, 10).tolist() == [[5, 9], [2, 3], [8, 10]]
    assert TR.merged(np.zeros((0, 2), np.int64)).shape == (0, 2)


def _trace():
    # two steps on the host clock: [0, 100) and [200, 300)
    steps = np.asarray([[0, 100], [200, 300]], np.int64)
    chip = TR.Chip(
        ops=[(10, 30, "fusion.1"), (20, 40, "all-to-all.3"),
             (90, 130, "paged_decode"),       # runs past its step's end
             (150, 160, "copy.9"),            # between steps: not counted
             (210, 260, "fusion.1")],
        modules=[(10, 95, "jit_decode_step"), (205, 265, "jit_prefill_step")])
    host = [(0, 100, TR.STEP), (200, 300, TR.STEP)]
    return TR.Trace([chip], host, steps)


def test_reduce_by_hand():
    red = TR.reduce(_trace())
    assert red.step_ns == 200
    # step 0 busy [10, 40) + [90, 100); step 1 busy [210, 260)
    assert red.busy_ns == 30 + 10 + 50
    assert red.idle_share() == pytest.approx(1 - 90 / 200)
    # first to last step: [10, 40) [90, 130) [150, 160) [210, 260)
    assert red.busy_window_ns == 30 + 40 + 10 + 50
    assert red.op_ns == {"fusion.1": 70.0, "all-to-all.3": 20.0,
                         "paged_decode": 40.0}
    assert red.collective_ns == 20.0
    assert red.modules_matching(r"decode_step") == (85.0, 1.0)
    assert red.modules_matching(r"step") == (145.0, 2.0)
    assert red.ops_matching(kernels.PAGED_ATTENTION) == 40.0
    # idle gaps of chip 0 inside the steps
    assert sorted(red.idle_gaps) == [(0, 10), (40, 90), (200, 210),
                                     (260, 300)]


def test_idle_by_innermost_span():
    red = TR.reduce(_trace())
    spans = [(0, 100, "step"), (30, 95, "decode"), (200, 300, "step"),
             (250, 300, "observe")]
    got = TR.idle_by_span(red, spans)
    assert got == {"step": 10 + 10, "decode": 50, "observe": 40}
    assert TR.top(got, 2, scale=1.0) == [["decode", 50], ["observe", 40]]


def test_reduce_averages_over_chips():
    tr = _trace()
    other = TR.Chip(ops=[(10, 50, "all-reduce.1")],
                    modules=[(10, 50, "jit_decode_step")])
    tr = TR.Trace([tr.chips[0], other], tr.host, tr.steps)
    red = TR.reduce(tr)
    assert red.chips == 2
    assert red.busy_ns == (90 + 40) / 2
    assert red.collective_ns == (20 + 40) / 2
    assert red.modules_matching(r"decode_step") == ((85 + 40) / 2, 1.0)


def test_recorded_chip_trace():
    tr = TR.load(SMALL)
    assert len(tr.chips) == 1 and len(tr.steps) == 3
    # the device clock runs behind the host's; every program lands inside
    # the step that launched it once shifted
    assert 1_000_000 < tr.chips[0].offset_ns < 2_000_000
    red = TR.reduce(tr)
    assert red.module_calls == {"jit__lambda": 6.0}
    assert 0 < red.busy_ns < red.step_ns
    assert red.ops_matching(kernels.PAGED_ATTENTION) > 0
    assert red.collective_ns == 0
    labels = TR.labelled(red.op_ns)
    assert "%paged_decode_attention.1 = bf16[4,2,4,128] custom-call" in labels


def test_op_label():
    name = ("%fusion.224 = bf16[8,32,14336]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[4,8,4096,14336]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element)")
    assert TR.op_label(name) == "%fusion.224 = bf16[8,32,14336] fusion"
