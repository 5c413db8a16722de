"""The two forest walks of ``repro.ml.trees``: level-wise selects for
complete trees up to ``SELECT_MAX_DEPTH`` deep, the node walk for every
other ensemble (complete heaps deeper than that, with implicit children,
and node arrays of any shape, with explicit ones). On complete trees both
must give the same leaf indices bit for bit, so every prediction built on
them is the same; ``SELECT_MAX_DEPTH`` set to 0 routes every tree through
the node walk, so the tests compare the two directly. Node arrays (fully
grown forests, scikit-learn's) are checked against a numpy walk."""

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
    node walk."""
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
    same forest takes the node walk and its gathers."""
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


def _grown_forest(n_trees=8, n_rows=2000, n_feat=N_FEAT, seed=0):
    """(forest as node arrays, training rows): fully grown random-split
    trees on noisy labels. A node whose rows hold both classes splits on
    a random feature at a uniform threshold between its rows' extremes
    (a leaf where that feature is constant); nodes are numbered breadth
    first, each split's children side by side; trees are padded to the
    largest with leaves."""
    import collections
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    y = (x[:, 0] + 1.5 * rng.normal(size=n_rows) > 0).astype(np.int64)
    trees, depth = [], 0
    for _ in range(n_trees):
        feat, thresh, left, leaf = [0], [np.inf], [0], [None]
        queue = collections.deque([(0, np.arange(n_rows), 0)])
        while queue:
            node, rows, d = queue.popleft()
            leaf[node] = np.bincount(y[rows], minlength=2)
            depth = max(depth, d)
            f = int(rng.integers(n_feat))
            lo, hi = x[rows, f].min(), x[rows, f].max()
            if leaf[node].min() == 0 or lo == hi:
                left[node] = node
                continue
            t = min(np.float32(rng.uniform(lo, hi)),
                    np.nextafter(hi, np.float32(-np.inf)))
            feat[node], thresh[node], left[node] = f, t, len(feat)
            go = x[rows, f] <= t
            for part in (rows[go], rows[~go]):
                queue.append((len(feat), part, d + 1))
                feat.append(0), thresh.append(np.inf)
                left.append(len(left)), leaf.append(None)
        trees.append((feat, thresh, left, leaf))
    m = max(len(t[0]) for t in trees)
    arr = {k: np.zeros((n_trees, m), np.int32) for k in ("feat", "left")}
    arr["left"][:] = np.arange(m)
    arr["thresh"] = np.full((n_trees, m), np.inf, np.float32)
    arr["leaf"] = np.zeros((n_trees, m, 2), np.float32)
    for i, (feat, thresh, left, leaf) in enumerate(trees):
        k = len(feat)
        arr["feat"][i, :k], arr["thresh"][i, :k] = feat, thresh
        arr["left"][i, :k], arr["leaf"][i, :k] = left, np.stack(leaf)
    ens = TreeEnsemble(feat=jnp.asarray(arr["feat"]),
                       thresh=jnp.asarray(arr["thresh"]),
                       leaf=jnp.asarray(arr["leaf"]), kind="rf",
                       n_classes=2, left=jnp.asarray(arr["left"]),
                       depth=depth)
    return ens, x


def _numpy_node_walk(ens, x):
    """(T, N) leaf node per tree, walked until the node is its own
    ``left``, with float32 compares (``x > t`` goes right)."""
    feat, thresh, left = (np.asarray(a) for a in (ens.feat, ens.thresh,
                                                  ens.left))
    out = np.empty((feat.shape[0], len(x)), np.int64)
    for t in range(feat.shape[0]):
        node = np.zeros(len(x), np.int64)
        for _ in range(feat.shape[1]):
            inner = left[t, node] != node
            if not inner.any():
                break
            step = left[t, node] + (x[np.arange(len(x)), feat[t, node]]
                                    > thresh[t, node])
            node = np.where(inner, step, node)
        out[t] = node
    return out


@pytest.mark.parametrize("bound", [trees.SELECT_MAX_DEPTH, 0])
def test_node_walk_matches_a_numpy_walk_on_grown_forests(monkeypatch,
                                                          bound):
    """Eight fully grown trees on 2,000 rows, 20 or more levels deep: the
    node walk's leaves are a numpy walk's, whatever ``SELECT_MAX_DEPTH``
    says (node arrays never take the selects); every training row lands
    in a pure leaf of its own class; the rf answer is the class of
    highest mean leaf fraction, the first on a tie."""
    monkeypatch.setattr(trees, "SELECT_MAX_DEPTH", bound)
    ens, xtr = _grown_forest()
    assert ens.depth >= 20 and not ens.complete
    x = _probe_rows(ens, np.concatenate([xtr[:500], np.random.default_rng(
        1).normal(size=(500, N_FEAT)).astype(np.float32)]))
    idx = np.asarray(tree_leaf_indices(ens, x))
    want = _numpy_node_walk(ens, x)
    np.testing.assert_array_equal(idx, want)
    leaf = np.asarray(ens.leaf).astype(np.float64)
    frac = np.stack([leaf[t, want[t]] for t in range(ens.n_trees)])
    frac = (frac / frac.sum(-1, keepdims=True)).mean(axis=0)
    sure = np.abs(frac[:, 0] - frac[:, 1]) > 1e-6
    pred = np.asarray(predict_tree_ensemble(ens, x))
    np.testing.assert_array_equal(pred[sure], frac.argmax(axis=1)[sure])
    train = np.stack([leaf[t, want[t, :500]] for t in range(ens.n_trees)])
    assert (train.min(axis=-1) == 0).all()               # pure leaves


def test_node_arrays_need_their_depth():
    ens, _ = _grown_forest(n_trees=1, n_rows=50)
    with pytest.raises(ValueError, match="depth"):
        TreeEnsemble(feat=ens.feat, thresh=ens.thresh, leaf=ens.leaf,
                     left=ens.left)


def test_sklearn_thresholds_round_down_to_float32():
    """Each float64 threshold becomes the largest float32 not above it:
    a float32 row decides ``x <= t`` as against the float64 value, on
    either side of every rounding."""
    rng = np.random.default_rng(0)
    t64 = np.concatenate([rng.normal(size=1000),
                          rng.normal(size=1000).astype(np.float32)
                          .astype(np.float64), [0.0, -0.0, 1e-300]])
    t32 = trees._f32_at_most(t64)
    assert t32.dtype == np.float32 and (t32.astype(np.float64) <= t64).all()
    up = np.nextafter(t32, np.float32(np.inf))
    assert (up.astype(np.float64) > t64).all()
    for x in (t32, up, np.nextafter(t32, np.float32(-np.inf))):
        np.testing.assert_array_equal(x <= t32,
                                      x.astype(np.float64) <= t64)


@pytest.mark.parametrize("n_estimators", [1, 8])
def test_from_sklearn_forest_predicts_as_extra_trees(n_estimators):
    """A fitted ``ExtraTreesClassifier`` read as node arrays walks to the
    leaves scikit-learn's ``apply`` finds and predicts what its
    ``predict`` predicts, on held-out float32 rows."""
    ensemble = pytest.importorskip("sklearn.ensemble")
    from repro.ml.trees import from_sklearn_forest
    rng = np.random.default_rng(3)
    x = rng.normal(size=(3000, 10)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] + rng.normal(size=3000) > 0)
    clf = ensemble.ExtraTreesClassifier(n_estimators=n_estimators,
                                        random_state=0).fit(x[:2000], y[:2000])
    ens = from_sklearn_forest(clf)
    assert not ens.complete and ens.n_trees == n_estimators
    assert ens.depth == max(e.tree_.max_depth for e in clf.estimators_)
    xte = x[2000:]
    idx = np.asarray(tree_leaf_indices(ens, xte))
    leaf = np.asarray(ens.leaf)
    for t, est in enumerate(clf.estimators_):    # the same leaf's value
        np.testing.assert_array_equal(
            leaf[t, idx[t]], est.tree_.value[est.apply(xte), 0, :]
            .astype(np.float32))
    np.testing.assert_array_equal(np.asarray(predict_tree_ensemble(ens, xte)),
                                  clf.predict(xte).astype(np.int32))
