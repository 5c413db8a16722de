"""``correct`` fails when it should: the control (the reference in
bfloat16 in the program's place) and faults planted in the timed path
(half of each call's answers dropped, one answer altered where the step
combines them) all come out as not correct, while the program as it is
comes out correct. Runs the whole harness on the CPU at toy sizes."""

from __future__ import annotations

import ml_dtypes
import pytest

from chipbench import control, run
from chipbench.tests.tiny import tiny_spec


@pytest.mark.parametrize("seed", [2**32 + 5, 17])
def test_control_fails_and_program_passes(seed):
    spec = tiny_spec("jane_fin.b2048")
    r = control.readings(spec, seed, 0.5)
    limits = spec["config"]["limits"]
    assert all(r["program"][k] <= limits[k] for k in limits)
    assert any(r["control"][k] > limits[k] for k in limits)
    assert r["control"]["pred_mismatch"] > 0


def _fin_half_batch(real):
    def f(sw, be, idx, valid):
        out = real(sw, be, idx, valid)
        return out.at[out.shape[0] // 2:].set(-1)
    return f


def _fin_answer_altered(real):
    def f(sw, be, idx, valid):
        out = real(sw, be, idx, valid)
        return out.at[0].set(1 - out[0])
    return f


FAULTS = [
    ("jane_fin.b2048", "repro.serving.hybrid_serving", "combine",
     _fin_half_batch),
    ("jane_fin.b2048", "repro.serving.hybrid_serving", "combine",
     _fin_answer_altered),
]


@pytest.mark.parametrize("workload,module,attr,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, _, _, f in FAULTS])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, workload,
                                                  module, attr, fault):
    import importlib
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, fault(getattr(mod, attr)))
    res = run.run_cell(tiny_spec(workload), 77, 0.5, False)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_dtype_is_below_the_configuration():
    assert ml_dtypes.finfo(ml_dtypes.bfloat16).nmant < 23
