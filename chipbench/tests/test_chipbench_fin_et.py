"""The finance deployment with a fully grown extra-trees backend
(``jane_fin_et``) on the CPU at toy sizes: the cell resolves to its
metrics, the maker grows what scikit-learn's defaults grow, the reference
walks what the program walks, the cell runs with 0 mismatches while its
control and a planted fault come out as not correct, and the new readers
read the dispatch counter and the forest."""

from __future__ import annotations

import importlib

import ml_dtypes
import numpy as np
import pytest

from chipbench import cells, grown, run
from chipbench.gen.janestreet import make_rows
from chipbench.reference import fin_et as ref
from chipbench.tests.tiny import ROOT, tiny_spec

CELL = "jane_fin_et.b2048"
PER_LAYER = {"fused_classify_us_per_krow.et", "step_rest_us_per_krow.et",
             "device_idle_pct.et", "classify_h2d_us_per_call.et",
             "backend_fill_pct.et", "backend_blocks_per_call.et",
             "walk_ns_per_visit.et", "classify_dispatch_us_per_call.et"}


def et_spec(train_rows: int = 2000, trees: int = 6):
    """``tiny_spec`` of the cell with the backend's own training rows and
    trees cut to a size the CPU grows in well under a second."""
    spec = tiny_spec(CELL)
    spec["config"]["models"]["backend"].update(train_rows=train_rows,
                                               trees=trees)
    return spec


def test_the_cells_resolve_to_their_metrics():
    bench = cells.load_benchmark(ROOT)
    spec = cells.resolve(bench, CELL, ROOT)
    assert cells.system(spec["config"]).__name__.endswith(".fin_et")
    assert {m["name"] for m in spec["end_to_end"]} == {"fin_p99_ms",
                                                      "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == PER_LAYER
    assert all(callable(cells.reader(m)) for m in PER_LAYER)
    assert spec["config"]["models"]["backend"] == {
        "kind": "extra_trees", "trees": 100, "max_depth": None,
        "max_features": "sqrt", "min_samples_leaf": 1, "bootstrap": False,
        "train_rows": 100000}


def _walk(forest, x):
    return ref.leaf_nodes(forest, x)


def test_the_maker_grows_pure_leaves_in_node_arrays():
    """Every training row walks to a leaf of its own class; each split's
    children sit side by side after it (breadth first); the depth is the
    deepest leaf's; the forest does not depend on the threads."""
    x, y = make_rows(3000, [5, 2])
    f = grown.grow_forest(x, y, n_trees=5, max_features=11, n_classes=2,
                          seed=[24, 4])
    t_, m = f["feat"].shape
    assert f["left"].shape == f["thresh"].shape == (t_, m) == (5, m)
    assert f["leaf"].shape == (5, m, 2) and m == f["nodes"].max()
    leaves = _walk(f, x)
    counts = np.stack([f["leaf"][t, leaves[t]] for t in range(5)])
    assert (counts[np.arange(5)[:, None], np.arange(3000), y] ==
            counts.sum(-1)).all()
    node = np.arange(m)
    for t in range(5):
        inner = (f["left"][t] != node) & (node < f["nodes"][t])
        assert (f["left"][t][inner] > node[inner]).all()
        assert np.isinf(f["thresh"][t][~inner]).all()
        kids = np.sort(np.concatenate([f["left"][t][inner],
                                       f["left"][t][inner] + 1]))
        np.testing.assert_array_equal(kids, np.arange(1, f["nodes"][t]))
    depth = np.zeros((5, m), np.int64)
    for t in range(5):
        for i in range(m):
            if f["left"][t, i] != i:
                depth[t, f["left"][t, i]:f["left"][t, i] + 2] = \
                    depth[t, i] + 1
    assert f["depth"] == depth.max()
    one = grown.grow_forest(x, y, n_trees=5, max_features=11, n_classes=2,
                            seed=[24, 4], workers=1)
    for k in ("feat", "thresh", "left", "leaf"):
        np.testing.assert_array_equal(one[k], f[k])


def test_the_maker_grows_as_many_nodes_as_scikit_learn():
    """At 20,000 training rows the maker's trees hold within 5% of the
    nodes a tree of scikit-learn's ``ExtraTreesClassifier`` holds at its
    defaults on the same rows, and are about as deep."""
    ensemble = pytest.importorskip("sklearn.ensemble")
    x, y = make_rows(20000, [24, 2])
    f = grown.grow_forest(x, y, n_trees=8, max_features=11, n_classes=2,
                          seed=[24, 4])
    clf = ensemble.ExtraTreesClassifier(n_estimators=8, random_state=0,
                                        n_jobs=4).fit(x, y)
    theirs = np.mean([e.tree_.node_count for e in clf.estimators_])
    assert abs(f["nodes"].mean() / theirs - 1) < 0.05
    assert abs(f["depth"] - max(e.tree_.max_depth
                                for e in clf.estimators_)) <= 8


def test_the_reference_walks_what_the_program_walks():
    """The program's node walk (``predict_tree_ensemble`` over node
    arrays) and the reference's give the same leaves and answers on rows
    the forest never saw."""
    import jax.numpy as jnp

    from repro.ml.trees import (TreeEnsemble, predict_tree_ensemble,
                                tree_leaf_indices)
    x, y = make_rows(2000, [6, 2])
    f = grown.grow_forest(x, y, n_trees=6, max_features=11, n_classes=2,
                          seed=1)
    ens = TreeEnsemble(feat=jnp.asarray(f["feat"]),
                       thresh=jnp.asarray(f["thresh"]),
                       leaf=jnp.asarray(f["leaf"]), kind="rf", n_classes=2,
                       left=jnp.asarray(f["left"]), depth=f["depth"])
    xs, _ = make_rows(3000, [7, 1])
    np.testing.assert_array_equal(np.asarray(tree_leaf_indices(ens, xs)),
                                  _walk(f, xs))
    pred, margin = ref.proba_margin(f, xs)
    sure = margin >= 1e-5
    assert sure.mean() > 0.8
    np.testing.assert_array_equal(
        np.asarray(predict_tree_ensemble(ens, xs))[sure], pred[sure])


@pytest.mark.parametrize("seed", [2**32 + 13, 29])
def test_control_fails_and_program_passes(seed):
    """The program served for one window passes; the control (the
    reference in bfloat16 in its place) fails over 80 calls, which cover
    a 20,000-row pool. At this size a few rows in a thousand reach the
    backend and bfloat16 flips a few of their answers, so the test takes
    a larger pool than ``tiny_spec``'s and forwards more rows (tau 0.9)."""
    spec = et_spec()
    spec["config"]["pool_rows"] = 20000
    spec["config"]["server"]["threshold"] = 0.9
    cell = cells.system(spec["config"]).CELL(spec, seed, lambda m: None)
    out = cell.serve(0.5)
    limits = spec["config"]["limits"]
    assert all(v <= limits[k] for k, v in cell.check(out).items())
    calls = cell.calls(cell.traffic, len(cell.pool), seed)
    rows = [next(calls)[1] for _ in range(80)]
    got = cell.control({"rows": rows}, ml_dtypes.bfloat16)
    assert got["pred_mismatch"] > limits["pred_mismatch"]


def test_the_switch_is_the_configurations_and_the_pool_the_runs():
    spec = et_spec()
    a = cells.system(spec["config"]).CELL(spec, 3, lambda m: None)
    b = cells.system(spec["config"]).CELL(spec, 4, lambda m: None)
    for k in ("feat", "thresh", "leaf"):
        np.testing.assert_array_equal(a.switch[k], b.switch[k])
    assert not np.array_equal(a.pool, b.pool)


def _backend_answer_flipped(real):
    def f(sw, be, idx, valid):
        return real(sw, be.at[0].set(1 - be[0]), idx, valid)
    return f


def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch):
    mod = importlib.import_module("repro.serving.hybrid_serving")
    monkeypatch.setattr(mod, "combine",
                        _backend_answer_flipped(mod.combine))
    res = run.run_cell(et_spec(), 77, 1.0, False)
    assert res["correct"] is False
    assert res["checks"]["pred_mismatch"]["value"] > 0


def test_the_cell_runs_correct_and_records_its_forest():
    """Run through the harness, the cell is correct; its record holds the
    dispatch counter per call and the forest's trees, depth and nodes,
    which the new readers read."""
    spec = et_spec()
    cell = cells.system(spec["config"]).CELL(spec, 31, lambda m: None)
    out = cell.serve(1.0)
    assert cell.check(out) == {"pred_mismatch": 0}
    rec = cell.results(out)["record"]
    be = cell.backend
    assert (rec["trees"], rec["depth"], rec["nodes"]) == (
        6, be["depth"], int(be["nodes"].sum()))
    assert len(rec["backend_rows"]) == out["calls"] >= 1
    blocks = [-(-r // 128) for r in rec["backend_rows"]]
    assert cells.reader("backend_blocks_per_call.et")(rec) == \
        pytest.approx(np.mean(blocks))


def test_the_readers_by_hand():
    class Trace:
        def op_ns(self, **kw):
            return 1_000_000

        def module_ns(self, pattern):
            return 3_000_000

    rec = {"backend_rows": [0, 1, 128, 129, 300], "capacity": 1024,
           "trees": 100, "depth": 50, "rows": 10240, "trace": Trace(),
           "keys": {"step": "x", "classify": {}}}
    blocks = cells.reader("backend_blocks_per_call.et")
    walk = cells.reader("walk_ns_per_visit.et")
    assert blocks(rec) == (0 + 1 + 1 + 2 + 3) / 5
    assert walk(rec) == pytest.approx(2_000_000 / (7 * 128 * 100 * 50))
    assert blocks(dict(rec, capacity=100, backend_rows=[5, 100])) == 1
    # silent where there is nothing to read: another system's record, a
    # window in which no block ran
    assert blocks({"rows": 1}) is None and walk({"rows": 1}) is None
    assert walk(dict(rec, trees=None)) is None
    assert walk(dict(rec, backend_rows=[0, 0])) is None


def test_a_program_without_node_arrays_fails_at_once(monkeypatch):
    """Where the program's ``TreeEnsemble`` has no ``left``, the cell
    stops before it grows anything."""
    import dataclasses

    from chipbench.systems import fin_et
    from repro.ml import trees

    @dataclasses.dataclass
    class Old:
        feat: object = None

    monkeypatch.setattr(trees, "TreeEnsemble", Old)
    monkeypatch.setattr(fin_et, "make_models", lambda cfg: 1 / 0)
    with pytest.raises(RuntimeError, match="node-array"):
        fin_et.EtCell(et_spec(), 1, lambda m: None)


def test_only_the_default_extra_trees_are_grown():
    from chipbench.systems import fin_et
    spec = et_spec()
    spec["config"]["models"]["backend"]["max_depth"] = 12
    with pytest.raises(ValueError, match="max_depth"):
        fin_et.make_models(spec["config"])


def test_the_scope_map_puts_the_node_walk_under_backend():
    """Compiled for the cell's rows, the node walk's four gathers a level
    (node feature, threshold and child, the row's value; a loop inside
    the blocks' loop) and the leaf read map to ``backend`` in the step's
    scope map, and the dispatch's own gather to ``dispatch``."""
    import re

    import jax
    import jax.numpy as jnp
    spec = et_spec()
    srv = cells.system(spec["config"]).CELL(spec, 5, lambda m: None).server
    n = spec["traffic"]["batch"]
    scopes = srv.step_scopes(n, 130)
    text = srv._step.lower(srv.artifact,
                           jax.ShapeDtypeStruct((n, 130), jnp.float32),
                           srv._tau, srv._backend).compile().as_text()
    gathers = {m.group(1): m.group(2) for m in re.finditer(
        r'%([\w.\-]+) = [^\n]* gather\([^\n]*op_name="([^"]*)"', text)}
    walk = [g for g, op in gathers.items() if "while/body/while/body" in op]
    leaf = [g for g, op in gathers.items() if "take_along_axis" in op]
    assert len(walk) == 4 and leaf
    assert {scopes[g] for g in walk + leaf} == {"backend"}
    assert "dispatch" in {scopes[g] for g in gathers}
