"""The check that decides ``correct``, on the CPU at a tiny size of the
same architecture (Pallas interpreted): a whole run of the harness
(engine prefill, paged decode over eight slots, EP dispatch) agrees with
the float32 reference; the fp8 control, judged in the program's place, does not; and
each fault a one-chip serving cell can have, planted under the timed path
(``faults.py``), makes ``correct`` false through the numbers that compare
served tokens with the reference."""

import json
import os

import numpy as np
import pytest

import faults
import run as R

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 31 + 977
SECONDS = 3.0


def _load(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


SPEC = {"configs": [{"name": "tiny-1c",
                     "file": "bench/tests/data/tiny-1c.json"}],
        "workloads": [{"name": "tiny.cell", "config": "tiny-1c",
                       "traffic": "tiny", "chips": 1}],
        "end_to_end": [], "per_layer": []}


def _run(hook=None, control=False, seed=SEED):
    return R.run("tiny.cell", seed, SECONDS, False, require_tpu=False,
                 spec=SPEC, mix=_load("tiny-closed"),
                 engine_hook=hook, control=control)


def _failed_gap_checks(res):
    conf = _load("tiny-1c")
    return [n for n in conf["limits"] if n in R.gap_stats(np.zeros(1))
            and res["checks"][n]["value"] > res["checks"][n]["limit"]]


def test_program_agrees_with_the_reference():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["served"]["checked_slots"] == 8
    assert res["checks"]["checked_slots_per_half"]["value"] == 4
    assert res["checks"]["served_tokens"]["value"] >= 100
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_sample_spreads_over_the_slots():
    def req(i, slot, n):
        r = R.Req(i, np.zeros(8, np.int32), 0.0, slot=slot, finished=True)
        r.sr = type("SR", (), {"generated": list(range(n))})()
        return r
    reqs = [req(0, 0, 300), req(1, 1, 40), req(2, 1, 50), req(3, 5, 10),
            req(4, 6, 20)]
    picked = R.sample(reqs, SEED)
    assert (picked[0].req.index, picked[0].fed, picked[0].checked) \
        == (0, 300, R.SAMPLE_TAIL)
    assert sorted(p.req.slot for p in picked) == [0, 1, 5, 6]
    assert all(p.fed == p.checked == min(R.SAMPLE_PREFIX, len(p.req.served))
               for p in picked[1:])
    assert R.slots_per_half(picked, 8) == 2
    assert R.slots_per_half(picked[:2], 8) == 0


def test_fp8_control_is_not_correct():
    res = _run(control=True)
    assert not res["correct"]
    assert _failed_gap_checks(res), res["checks"]
    assert res["readings"]["program_mismatch_share"] \
        < res["readings"]["mismatch_share"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    res = _run(hook=faults.FAULTS[fault])
    assert not res["correct"]
    assert _failed_gap_checks(res), res["checks"]
