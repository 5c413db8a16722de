"""The two forest walks of ``repro.ml.trees``: level-wise selects for trees
up to ``SELECT_MAX_DEPTH`` deep, the heap walk beyond. Both must give the
same leaf indices bit for bit, so every prediction built on them is the
same; the heap walk stays in the module, so the tests compare against it
directly (``SELECT_MAX_DEPTH`` set to 0 routes every tree through it)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ml import trees
from repro.ml.trees import (TreeEnsemble, fit_decision_tree,
                            fit_isolation_forest, fit_random_forest,
                            fit_xgboost, predict_iforest_score,
                            predict_margin_xgboost,
                            predict_proba_tree_ensemble,
                            predict_tree_ensemble, tree_leaf_indices)

N_FEAT = 5


@pytest.fixture(scope="module")
def data():
    from repro.data.unsw_like import make_unsw_like, train_test_split
    x, y = make_unsw_like(3000, seed=4, n_features=N_FEAT)
    xtr, ytr, xte, _ = train_test_split(x, y)
    return np.asarray(xtr), np.asarray(ytr), np.asarray(xte)


def _fit(kind, depth, xtr, ytr):
    if kind == "dt":
        return fit_decision_tree(xtr, ytr, n_classes=2, max_depth=depth)
    if kind == "rf":
        return fit_random_forest(xtr, ytr, n_classes=2, n_trees=4,
                                 max_depth=depth, seed=depth)
    if kind == "xgb":
        return fit_xgboost(xtr, ytr, n_trees=3, max_depth=depth)
    return fit_isolation_forest(xtr, n_trees=4, max_depth=depth, seed=depth)


def _probe_rows(ens, xte, n=1000, seed=0):
    """A ragged batch: held-out rows, rows sitting on the ensemble's own
    thresholds, and rows with +-inf, NaN, -0.0 and subnormals."""
    rng = np.random.default_rng(seed)
    x = xte[rng.integers(0, len(xte), n)].astype(np.float32)
    feat, thresh = np.asarray(ens.feat), np.asarray(ens.thresh)
    for f in range(x.shape[1]):
        on = thresh[(feat == f) & np.isfinite(thresh)]
        if on.size:
            rows = rng.choice(n, n // 4, replace=False)
            x[rows, f] = rng.choice(on, rows.size)
    special = np.float32([np.inf, -np.inf, np.nan, -0.0, 0.0, 1e-45,
                          -1e-45])
    mask = rng.random(x.shape) < 0.05
    x[mask] = rng.choice(special, int(mask.sum()))
    return x


def _scores(ens, x):
    if ens.kind in ("dt", "rf"):
        return predict_proba_tree_ensemble(ens, x)
    if ens.kind == "xgb":
        return predict_margin_xgboost(ens, x)
    return predict_iforest_score(ens, x)


def _both_walks(ens, x, monkeypatch):
    """(leaf indices, scores, predictions) on the selects, then on the
    heap walk."""
    out = []
    for bound in (trees.SELECT_MAX_DEPTH, 0):
        monkeypatch.setattr(trees, "SELECT_MAX_DEPTH", bound)
        out.append([np.asarray(tree_leaf_indices(ens, x)),
                    np.asarray(_scores(ens, x)),
                    np.asarray(predict_tree_ensemble(ens, x))])
    return out


@pytest.mark.parametrize("depth", [1, 4, 8, trees.SELECT_MAX_DEPTH])
@pytest.mark.parametrize("kind", ["dt", "rf", "xgb", "iforest"])
def test_select_walk_matches_heap_walk(data, monkeypatch, kind, depth):
    xtr, ytr, xte = data
    ens = _fit(kind, depth, xtr, ytr)
    assert ens.kind == kind and ens.depth == depth
    x = _probe_rows(ens, xte)
    (idx, score, pred), (idx0, score0, pred0) = _both_walks(ens, x,
                                                            monkeypatch)
    assert idx.dtype == idx0.dtype == np.int32
    assert idx.shape == (ens.n_trees, len(x))
    np.testing.assert_array_equal(idx, idx0)
    np.testing.assert_allclose(score, score0, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(pred, pred0)


def _dense_forest(depth, n_trees=6, seed=1, n_feat=N_FEAT):
    """Random trees split at every node, thresholds including +-inf, -0.0
    and a subnormal, three classes."""
    rng = np.random.default_rng(seed)
    h = (1 << depth) - 1
    thresh = rng.normal(size=(n_trees, h)).astype(np.float32)
    pick = rng.random(thresh.shape)
    thresh[pick < 0.04] = np.inf
    thresh[(pick >= 0.04) & (pick < 0.08)] = -np.inf
    thresh[(pick >= 0.08) & (pick < 0.12)] = -0.0
    thresh[(pick >= 0.12) & (pick < 0.14)] = 1e-45
    return TreeEnsemble(
        feat=jnp.asarray(rng.integers(0, n_feat, (n_trees, h)), jnp.int32),
        thresh=jnp.asarray(thresh),
        leaf=jnp.asarray(rng.integers(0, 9, (n_trees, h + 1, 3)),
                         jnp.float32),
        kind="rf", n_classes=3)


@pytest.mark.parametrize("depth", [2, 6, trees.SELECT_MAX_DEPTH])
def test_select_walk_matches_heap_walk_on_dense_trees(monkeypatch, depth):
    """Every node splits, so every level's pick is exercised; rows sit on
    the thresholds, on either side of -0.0/0.0 and at +-inf and NaN."""
    ens = _dense_forest(depth)
    rng = np.random.default_rng(depth)
    x = rng.normal(size=(1000, N_FEAT)).astype(np.float32)
    x[::3] = rng.choice(np.asarray(ens.thresh).ravel(), x[::3].shape)
    (idx, score, pred), (idx0, score0, pred0) = _both_walks(
        ens, _probe_rows(ens, x, seed=depth), monkeypatch)
    np.testing.assert_array_equal(idx, idx0)
    np.testing.assert_allclose(score, score0, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(pred, pred0)


@pytest.mark.parametrize("depth", [6, 8])
def test_select_walk_matches_heap_walk_on_wide_rows(monkeypatch, depth):
    """The program's finance backend shape: 60 XGBoost trees on all 130
    features, so each level's value pick runs over 130 features."""
    dense = _dense_forest(depth, n_trees=60, n_feat=130)
    ens = dataclasses.replace(dense, leaf=dense.leaf[..., :1], kind="xgb",
                              learning_rate=0.1)
    rng = np.random.default_rng(depth)
    x = rng.normal(size=(1000, 130)).astype(np.float32)
    x[::3] = rng.choice(np.asarray(ens.thresh).ravel(), x[::3].shape)
    (idx, score, pred), (idx0, score0, pred0) = _both_walks(
        ens, _probe_rows(ens, x, seed=depth), monkeypatch)
    np.testing.assert_array_equal(idx, idx0)
    np.testing.assert_allclose(score, score0, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(pred, pred0)


def test_row_blocks_match_one_block(monkeypatch):
    """A batch split into blocks of rows (padded at the end) gives what
    one block gives."""
    ens = _dense_forest(6)
    x = _probe_rows(ens, np.random.default_rng(2).normal(
        size=(1000, N_FEAT)).astype(np.float32))
    one = [np.asarray(tree_leaf_indices(ens, x)),
           np.asarray(predict_proba_tree_ensemble(ens, x))]
    monkeypatch.setattr(trees, "_BLOCK_ELEMS", 1)      # 128-row blocks
    blocked = [np.asarray(tree_leaf_indices(ens, x)),
               np.asarray(predict_proba_tree_ensemble(ens, x))]
    for a, b in zip(one, blocked):
        np.testing.assert_array_equal(a, b)


def _gathers(ens, n_rows):
    rows = jax.ShapeDtypeStruct((n_rows, N_FEAT), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda r: predict_tree_ensemble(ens, r))(rows)
    return str(jaxpr).count(" gather[")


def test_backend_shape_takes_the_gather_free_walk():
    """The benchmark's backend shape (32 trees of depth 8 on 1,024 rows of
    5 features) traces to no gather at all; one level past the bound the
    same forest takes the heap walk and its gathers."""
    assert _gathers(_dense_forest(8, n_trees=32), 1024) == 0
    deep = _dense_forest(trees.SELECT_MAX_DEPTH + 1, n_trees=32)
    assert _gathers(deep, 1024) > 0


def test_boosted_margins_in_row_blocks_match_a_numpy_heap_walk(monkeypatch):
    """The finance backend's shape, cut in trees: XGBoost trees of depth 11
    on 130 features, walked by the selects with the row blocks engaged
    (128-row blocks, the last padded), against a numpy heap walk with
    float32 compares and a float64 sum. The leaves agree exactly; the
    margins to the float32 rounding of an 8-term sum. Rows hold +-inf,
    NaN and -0.0 but no subnormal, which XLA flushes to zero and numpy
    does not."""
    depth, n_feat = 11, 130
    dense = _dense_forest(depth, n_trees=8, n_feat=n_feat, seed=3)
    ens = dataclasses.replace(dense, leaf=dense.leaf[..., :1] - 4.0,
                              kind="xgb", learning_rate=0.05,
                              base_score=0.1)
    rng = np.random.default_rng(11)
    x = rng.normal(size=(300, n_feat)).astype(np.float32)
    x[::3] = rng.choice(np.asarray(ens.thresh).ravel(), x[::3].shape)
    mask = rng.random(x.shape) < 0.05
    x[mask] = rng.choice(np.float32([np.inf, -np.inf, np.nan, -0.0, 0.0]),
                         int(mask.sum()))
    x[np.abs(x) < np.finfo(np.float32).tiny] = 0.0
    monkeypatch.setattr(trees, "_BLOCK_ELEMS", 1)      # 128-row blocks
    idx = np.asarray(tree_leaf_indices(ens, x))
    margin = np.asarray(predict_margin_xgboost(ens, x))
    pred = np.asarray(predict_tree_ensemble(ens, x))

    feat, thresh = np.asarray(ens.feat), np.asarray(ens.thresh)
    leaf = np.asarray(ens.leaf)[..., 0].astype(np.float64)
    rows = np.arange(len(x))
    want_idx = np.empty_like(idx)
    total = np.zeros(len(x))
    size = np.zeros(len(x))
    for t in range(ens.n_trees):
        node = np.zeros(len(x), np.int64)
        for _ in range(depth):
            node = 2 * node + 1 + (x[rows, feat[t, node]] > thresh[t, node])
        want_idx[t] = node - feat.shape[1]
        total += leaf[t, want_idx[t]]
        size += np.abs(leaf[t, want_idx[t]])
    want = 0.1 + 0.05 * total
    np.testing.assert_array_equal(idx, want_idx)
    band = ens.n_trees * 2.0 ** -24 * 0.05 * size + 2.0 ** -24 * 0.1
    assert (np.abs(margin - want) <= band).all()
    sure = np.abs(want) > band
    assert sure.mean() > 0.9 and 0 < pred[sure].mean() < 1
    np.testing.assert_array_equal(pred[sure], (want > 0)[sure])
