"""chip_smoke.py at toy size on CPU: its phases run end to end and its
checks hold, so the script does not rot between chip runs. On CPU every
server resolves to the XLA references, so the TPU-only checks (fused
kernel, ``tpu_custom_call``) are the script's ``main`` and are not
exercised here; ``main`` itself must refuse to run without a TPU."""

import json

import pytest

import chip_smoke


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert "no TPU" in out.err
    last = out.out.strip().splitlines()[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)


def test_stream_phase_toy():
    res = chip_smoke.stream_phase(0, n_flows=1500, n_buckets=4096)
    assert res["impl"] == "ref" and res["use_pallas"] is False
    assert res["packets"] > 0 and 0.0 < res["fraction_handled"] <= 1.0


def test_finance_phase_toy():
    res = chip_smoke.finance_phase(0, n_rows=12000)
    assert res["impl"] == "ref"


def test_sharded_phase_toy():
    chip_smoke.sharded_phase(0, n_flows=1500, n_buckets=4096,
                             meshes=((1, 1),))


def test_same_reports_bitwise_equality():
    import numpy as np
    a = np.array([[1.0, np.nan], [2.0, 3.0]], np.float32)
    assert chip_smoke.same(a, a.copy())
    b = a.copy()
    b[1, 1] = np.nextafter(np.float32(3.0), np.float32(4.0))
    assert not chip_smoke.same(a, b)
    assert not chip_smoke.same(a, a.astype(np.float64))
