"""The finance deployment with its XGBoost backend (``jane_fin_xgb``) on
the CPU at toy sizes: the cell resolves to its metrics, runs with 0
mismatches, its control and planted faults come out as not correct, the
fill reader reads the dispatch counter, and the step's scope map puts the
boosted walk under ``backend``."""

from __future__ import annotations

import importlib

import ml_dtypes
import numpy as np
import pytest

from chipbench import boost, cells, run
from chipbench.reference import fin_xgb as ref
from chipbench.tests.tiny import ROOT, tiny_spec

CELL = "jane_fin_xgb.b2048"
PER_LAYER = {"fused_classify_us_per_krow.xgb", "step_rest_us_per_krow.xgb",
             "device_idle_pct.xgb", "classify_h2d_us_per_call.xgb",
             "backend_fill_pct.xgb"}


def test_the_cell_resolves_to_its_metrics():
    spec = cells.resolve(cells.load_benchmark(ROOT), CELL, ROOT)
    assert cells.system(spec["config"]).__name__.endswith(".fin_xgb")
    assert {m["name"] for m in spec["end_to_end"]} == {"fin_p99_ms",
                                                      "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == PER_LAYER
    assert all(callable(cells.reader(m)) for m in PER_LAYER)
    cfg = spec["config"]
    assert cfg["models"]["backend"] == {
        "kind": "xgb", "trees": 500, "depth": 11, "learning_rate": 0.05,
        "subsample": 0.9, "colsample_bytree": 0.7, "base_score": 0.0}
    assert cfg["server"]["switch_features"] == [42, 43, 45, 124, 126]


def test_the_maker_draws_live_splits_from_the_column_sample():
    """Each tree's node features lie in one 91-of-130 column sample, and
    a node's threshold is a value of its feature at a training row."""
    x = np.random.default_rng(0).normal(size=(3000, 130)).astype(np.float32)
    f = boost.make_boosted(x, n_trees=6, depth=7, learning_rate=0.05,
                           subsample=0.9, colsample_bytree=0.7,
                           base_score=0.0, seed=4)
    assert f["feat"].shape == f["thresh"].shape == (6, 127)
    assert f["leaf"].shape == (6, 128)
    for t in range(6):
        assert len(set(f["feat"][t])) <= 91
        assert all(v in x[:, c] for c, v in zip(f["feat"][t][:15],
                                                 f["thresh"][t][:15]))
    # the root's split sends training rows both ways in every tree
    root = x[np.arange(3000)[:, None], f["feat"][:, 0]] > f["thresh"][:, 0]
    assert root.any(axis=0).all() and (~root).any(axis=0).all()


@pytest.mark.parametrize("seed", [2**32 + 9, 23])
def test_control_fails_and_program_passes(seed):
    """The program served for one window passes; the control (the
    reference in bfloat16 in its place) fails over the first 16 calls of
    the cell's traffic. In bfloat16 about 2% of the backend's answers
    and 0.3-0.6% of the switch's confidences change, about one mismatch
    in five calls of 256 rows, so it takes more calls than a CPU window
    holds."""
    spec = tiny_spec(CELL)
    cell = cells.system(spec["config"]).CELL(spec, seed, lambda m: None)
    out = cell.serve(0.5)
    limits = spec["config"]["limits"]
    assert all(v <= limits[k] for k, v in cell.check(out).items())
    calls = cell.calls(cell.traffic, len(cell.pool), seed)
    rows = [next(calls)[1] for _ in range(16)]
    got = cell.control({"rows": rows}, ml_dtypes.bfloat16)
    assert got["pred_mismatch"] > limits["pred_mismatch"]


def _backend_answer_flipped(real):
    """The first buffer slot's backend answer flipped where the step
    combines them (the slot holds a forwarded row whenever any is)."""
    def f(sw, be, idx, valid):
        return real(sw, be.at[0].set(1 - be[0]), idx, valid)
    return f


def _switch_columns_shifted(real):
    """The switch reads the column after each it should parse."""
    def f(x, cols):
        return real(x, None if cols is None else [c + 1 for c in cols])
    return f


@pytest.mark.parametrize("attr,fault", [
    ("combine", _backend_answer_flipped),
    ("switch_columns", _switch_columns_shifted)],
    ids=["backend_answer_flipped", "switch_columns_shifted"])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, attr, fault):
    mod = importlib.import_module("repro.serving.hybrid_serving")
    monkeypatch.setattr(mod, attr, fault(getattr(mod, attr)))
    res = run.run_cell(tiny_spec(CELL), 77, 1.0, False)
    assert res["correct"] is False
    assert res["checks"]["pred_mismatch"]["value"] > 0


def test_the_fill_reads_the_dispatch_counter():
    """``backend_fill_pct`` over the recorded ``backend_rows`` equals the
    rows the plain reference sends to the backend, over calls times
    capacity; the counter is read only after the window."""
    spec = tiny_spec(CELL)
    cell = cells.system(spec["config"]).CELL(spec, 31, lambda m: None)
    out = cell.serve(1.0)
    assert out["calls"] >= 1
    assert all(not isinstance(r, (int, np.integer))
               for r in out["backend_rows"])          # device arrays
    rec = cell.results(out)["record"]
    server = spec["config"]["server"]
    ans = ref.pool_answers(cell.pool, cell.switch, cell.backend,
                           server["switch_features"])
    want = []
    for idx in out["rows"]:
        fwd = ans["conf"][idx] < np.float32(server["threshold"])
        want.append(int((fwd & (np.cumsum(fwd) <= server["capacity"]))
                        .sum()))
    assert rec["backend_rows"] == want and sum(want) > 0
    fill = cells.reader("backend_fill_pct.xgb")(rec)
    assert fill == pytest.approx(100.0 * sum(want)
                                 / (len(want) * server["capacity"]))
    assert cells.reader("backend_fill_pct.xgb")({"rows": 1}) is None


def test_the_scope_map_puts_the_boosted_walk_under_backend(monkeypatch):
    """Compiled for the cell's rows with the row blocks engaged (a
    256-row buffer in 128-row blocks), every instruction of the step that
    the margin walk traced (the level selects, the leaf pick, the blocks'
    loop) maps to ``backend``."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.ml import trees
    monkeypatch.setattr(trees, "_BLOCK_ELEMS", 1)
    spec = tiny_spec(CELL)
    spec["config"]["server"]["capacity"] = 256
    srv = cells.system(spec["config"]).CELL(spec, 5, lambda m: None).server
    n = spec["traffic"]["batch"]
    scopes = srv.step_scopes(n, 130)
    text = srv._step.lower(srv.artifact,
                           jax.ShapeDtypeStruct((n, 130), jnp.float32),
                           jnp.float32(srv.threshold)).compile().as_text()
    walk = [m.group(1) for m in re.finditer(
        r'%([\w.\-]+) = [^\n]*op_name="[^"]*(?:_level_select|_leaf_pick'
        r'|while)[^"]*"', text)]
    assert walk and " while(" in text
    assert {scopes[w] for w in walk} == {"backend"}
