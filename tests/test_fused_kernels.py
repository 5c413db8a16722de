"""Single-matmul fused path: parity, padding, and artifact round-trips.

Covers the ISSUE-1 acceptance surface:
  * bit-exact parity vs kernels/ref.py for every agg mode
    (vote, wsum_sigmoid, iforest, svm_ovo, nb_log, kmeans);
  * both decision-select strategies (matmul and compare);
  * non-multiple-of-TILE_N batch sizes through the padded entry points;
  * lane-padded artifacts round-tripping through update_tables;
  * _pad_batch replicating the last row (never synthesizing zero rows).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.artifact import LANE, finalize_artifact, flatten_ftable
from repro.core.inference import table_predict
from repro.core.mapping import map_tree_ensemble
from repro.kernels import ensemble_lookup as ek
from repro.kernels import ref
from repro.kernels.ops import _pad_batch, fused_classify
from repro.kernels.tuning import TileConfig


def _fit_model(model, xtr, ytr):
    """-> (artifact, fitted model): 4 trees of depth 4 (the isolation
    forest 6), so every agg mode stays small."""
    from benchmarks.common import fit_and_map
    if model == "IForest":
        from repro.ml.trees import fit_isolation_forest
        ens = fit_isolation_forest(np.asarray(xtr), n_trees=6, max_depth=4,
                                   seed=0)
        return map_tree_ensemble(ens, xtr.shape[1]), ens
    _, art, m = fit_and_map(model, xtr, ytr, n_trees=4, max_depth=4)
    return art, m


def _fit_artifact(model, xtr, ytr):
    return _fit_model(model, xtr, ytr)[0]


ALL_MODELS = ("DT", "RF", "XGB", "IForest", "SVM", "Bayes", "KMeans")


@pytest.mark.parametrize("model", ALL_MODELS)
def test_fused_classify_all_aggs_bit_exact(model, anomaly_data):
    """Every agg mode: fused kernel (pred, conf) == pure-jnp inference."""
    xtr, ytr, xte, yte = anomaly_data
    art = _fit_artifact(model, xtr, ytr)
    p_ref, c_ref = table_predict(art, xte[:300])
    p_k, c_k = fused_classify(art, xte[:300], use_pallas=True,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_ref),
                               atol=1e-6)


@pytest.mark.parametrize("select", ["matmul", "compare"])
@pytest.mark.parametrize("model", ["RF", "XGB"])
def test_select_strategies_bit_exact(model, select, anomaly_data):
    """Both decision-select strategies return the oracle sums exactly."""
    xtr, ytr, xte, yte = anomaly_data
    art = _fit_artifact(model, xtr, ytr)
    vote = art.agg == "vote"
    dtable = (art.dtable_class if vote
              else art.dtable_value.q).astype(jnp.float32)
    x = jnp.asarray(xte[:256], jnp.float32)
    out = ek.ensemble_lookup_fused(
        x, art.edges, art.ftable_flat, art.dtable_flat, art.dtable_pad,
        interpret=True, select=select)
    expect = ref.ensemble_lookup_ref(x, art.edges, art.ftable, art.strides,
                                     dtable, n_classes=art.n_classes,
                                     vote=vote)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("n", [1, 7, 131, 257])
@pytest.mark.parametrize("model", ["RF", "SVM"])
def test_non_tile_multiple_batches(model, n, anomaly_data):
    """Ragged batches pad, classify, and slice back exactly."""
    xtr, ytr, xte, yte = anomaly_data
    art = _fit_artifact(model, xtr, ytr)
    p_ref, c_ref = table_predict(art, xte[:n])
    p_k, c_k = fused_classify(art, xte[:n], use_pallas=True, interpret=True)
    assert p_k.shape == (n,)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))
    np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_ref),
                               atol=1e-6)


def test_lane_padded_layout_shapes(anomaly_data):
    """finalize_artifact pads B/T/M/S to the lane multiple and keeps the
    logical view recoverable via pad_meta."""
    xtr, ytr, xte, yte = anomaly_data
    art = _fit_artifact("RF", xtr, ytr)
    base = dataclasses.replace(art, ftable_flat=None, vtable_flat=None,
                               dtable_flat=None, dtable_pad=None)
    lane = LANE
    art128 = finalize_artifact(base, lane=lane)
    f, b, t = art128.ftable.shape[0], art128.n_bins, art128.n_trees
    fb, t_pad = art128.ftable_flat.shape
    assert fb % (f * lane) == 0 and fb // f >= b
    assert t_pad % lane == 0 and t_pad >= t
    meta = art128.pad_meta
    assert meta["b_pad"] * f == fb and meta["t_pad"] == t_pad
    assert meta["s_pad"] % lane == 0 and meta["s_pad"] >= meta["s"]
    # padded layout classifies identically
    p_ref, c_ref = table_predict(art128, xte[:256])
    p_k, c_k = fused_classify(art128, xte[:256], use_pallas=True,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))


def test_flat_tables_match_gather_semantics():
    """flatten_ftable premultiplies strides; one-hot matmul == gather+dot."""
    rng = np.random.default_rng(3)
    f, u, t = 4, 6, 5
    ftable = rng.integers(0, 3, (f, u + 1, t)).astype(np.int32)
    strides = rng.integers(1, 9, (t, f)).astype(np.int32)
    flat = np.asarray(flatten_ftable(jnp.asarray(ftable),
                                     jnp.asarray(strides), lane=8))
    b_pad = flat.shape[0] // f
    bins = rng.integers(0, u + 1, (32, f))
    keys_ref = np.einsum("nft,tf->nt",
                         ftable[np.arange(f)[None, :], bins], strides)
    oh = np.zeros((32, f * b_pad), np.float32)
    for n in range(32):
        for fi in range(f):
            oh[n, fi * b_pad + bins[n, fi]] = 1.0
    keys = oh @ flat
    np.testing.assert_array_equal(keys[:, :t].astype(np.int64), keys_ref)


def test_update_tables_roundtrip_padded(anomaly_data):
    """Same-constraint retrains hot-swap (padded layouts included);
    changed constraints are rejected."""
    from repro.ml.trees import fit_random_forest, predict_tree_ensemble
    from repro.serving.hybrid_serving import HybridServer
    xtr, ytr, xte, yte = anomaly_data
    f = xtr.shape[1]
    a1 = map_tree_ensemble(
        fit_random_forest(xtr, ytr, n_classes=2, n_trees=4, max_depth=3,
                          seed=0), f)
    a2 = map_tree_ensemble(
        fit_random_forest(np.asarray(xtr)[::-1], np.asarray(ytr)[::-1],
                          n_classes=2, n_trees=4, max_depth=3, seed=0), f)
    srv = HybridServer(a1, lambda r: jnp.zeros(r.shape[0], jnp.int32),
                       threshold=0.9, capacity=64)
    same = all(jax.tree.leaves(jax.tree.map(
        lambda x, y: x.shape == y.shape, a1, a2)))
    if same:
        srv.update_tables(a2)                   # padded arrays swap too
        p, _ = srv.classify(xte[:100])
        assert p.shape == (100,)
    a3 = map_tree_ensemble(
        fit_random_forest(xtr, ytr, n_classes=2, n_trees=5, max_depth=3,
                          seed=0), f)
    with pytest.raises(ValueError):
        srv.update_tables(a3)                   # more trees -> new shapes


def test_pad_batch_replicates_last_row():
    x = jnp.arange(10, dtype=jnp.float32).reshape(5, 2)
    xp, n = _pad_batch(x, 4)
    assert n == 5 and xp.shape == (8, 2)
    np.testing.assert_array_equal(np.asarray(xp[5:]),
                                  np.tile(np.asarray(x[4]), (3, 1)))
    xp2, n2 = _pad_batch(x[:4], 4)
    assert n2 == 4 and xp2.shape == (4, 2)      # no pad when aligned


def test_tile_config_override_bit_exact(anomaly_data):
    """Nondefault tile sizes change nothing numerically."""
    xtr, ytr, xte, yte = anomaly_data
    art = _fit_artifact("RF", xtr, ytr)
    p_ref, c_ref = table_predict(art, xte[:200])
    for tiles in (TileConfig(tile_n=64, edge_chunk=8, dtable_chunk=128,
                             select="matmul"),
                  TileConfig(tile_n=256, edge_chunk=64, dtable_chunk=256,
                             select="compare")):
        p, c = fused_classify(art, xte[:200], use_pallas=True,
                              interpret=True, tiles=tiles)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
        np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref),
                                   atol=1e-6)


def test_classify_impl_routes_by_platform_and_fit(anomaly_data, monkeypatch):
    """classify_impl is the one routing decision fused_classify and
    classify_batch_rows share: None resolves by platform (CPU here: the
    reference), explicit kernels run tiles.impl, and an artifact past the
    VMEM budget takes the reference even with the kernels on."""
    from repro.kernels import ops
    xtr, ytr, _, _ = anomaly_data
    art = finalize_artifact(_fit_artifact("RF", xtr, ytr))
    assert ops.classify_impl(art) == "ref"
    assert ops.classify_impl(art, use_pallas=True) == "fused"
    assert ops.classify_impl(art, use_pallas=True,
                             tiles=TileConfig(impl="loop")) == "loop"
    assert ops.classify_batch_rows(art, 300, use_pallas=True) == 384
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.classify_impl(art) == "fused"
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", 0)
    assert ops.classify_impl(art) == "ref"
    assert ops.classify_batch_rows(art, 300) == 300


def test_sweep_best_raises_when_the_default_fails():
    """A failing non-default candidate is skipped; a failing default
    propagates (it is what would run), never an untimed 'winner'."""
    from repro.kernels.tuning import sweep_best

    def timer(c):
        if c == "broken":
            raise RuntimeError("refused by the compiler")
        return {"a": 2.0, "b": 1.0}[c]
    best, timings = sweep_best(["a", "broken", "b"], timer, default="a")
    assert best == "b" and set(timings) == {"a", "b"}
    with pytest.raises(RuntimeError, match="refused"):
        sweep_best(["a", "b"], timer, default="broken")
