"""The row pool and the closed-loop source: deterministic per seed, and
each call's rows and latency recorded as the source defined them."""

from __future__ import annotations

import itertools

import numpy as np

from chipbench import cells, run
from chipbench.gen.janestreet import N_FEATURES, SWITCH_FEATURES, make_rows
from chipbench.tests.tiny import tiny_spec


def test_rows_are_a_function_of_the_seed():
    a, ya = make_rows(500, [2**40 + 1, 1])
    b, yb = make_rows(500, [2**40 + 1, 1])
    c, _ = make_rows(500, [7, 1])
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ya, yb)
    assert not np.array_equal(a, c)
    assert a.shape == (500, N_FEATURES) and a.dtype == np.float32
    assert 0.05 < ya.mean() < 0.25                 # about 13 % positive
    assert max(SWITCH_FEATURES) < N_FEATURES


def test_closed_loop_calls_take_consecutive_rows_round_the_pool():
    calls = cells.source("closed_loop")
    got = list(itertools.islice(calls({"batch": 4}, 10, 1), 4))
    assert all(due is None for due, _ in got)
    rows = np.concatenate([idx for _, idx in got])
    np.testing.assert_array_equal(rows, np.arange(16) % 10)
    again = list(itertools.islice(calls({"batch": 4}, 10, 2**62), 4))
    for (_, x), (_, y) in zip(got, again):
        np.testing.assert_array_equal(x, y)


def test_the_window_records_each_call(monkeypatch):
    """Every call served in the window is recorded with its pool rows, its
    predictions and its latency, and the reference answers those rows."""
    spec = tiny_spec("jane_fin.b2048")
    seen = {}
    from chipbench.systems import fin_batch

    real = fin_batch.FinCell.results

    def results(self, out):
        seen.update(out)
        return real(self, out)

    monkeypatch.setattr(fin_batch.FinCell, "results", results)
    res = run.run_cell(spec, 99, 0.5, False)
    assert res["correct"]
    n = res["attempted"]
    assert n == seen["calls"] == len(seen["rows"]) == len(seen["preds"])
    assert len(seen["latency"]) == n and seen["latency"].min() > 0
    assert seen["latency"].sum() < 0.5 + 0.5
    batch = spec["traffic"]["batch"]
    np.testing.assert_array_equal(
        np.concatenate(seen["rows"]),
        np.arange(n * batch) % spec["config"]["pool_rows"])
    p99 = res["metrics"]["fin_p99_ms"]["value"]
    assert p99 == np.percentile(seen["latency"], 99) * 1e3
