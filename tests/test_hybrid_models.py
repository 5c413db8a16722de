"""The per-request tier across IIsy's model menu.

Every switch model (DT, RF, XGB, isolation forest, SVM, naive Bayes,
K-means) served by ``HybridServer`` on the fused step and on the
two-phase path, on rows of the artifact's width and on wider rows through
``switch_features``; and every backend kind run by the fused step over
the dispatch buffer's row blocks (``core.hybrid.backend_over_blocks``).
Both are held bit-equal to ``core.hybrid.hybrid_serve``, which evaluates
the backend on the whole buffer.
"""

import numpy as np
import pytest

from repro.core.hybrid import backend_block, hybrid_serve
from repro.core.inference import table_predict
from repro.ml.kmeans import predict_kmeans
from repro.ml.naive_bayes import predict_nb
from repro.ml.svm import predict_svm
from repro.ml.trees import fit_random_forest, predict_tree_ensemble
from repro.serving.hybrid_serving import HybridServer

from test_fused_kernels import ALL_MODELS, _fit_model
from test_hybrid_serving import COLS, _rows_forwarding, _wide_rows


@pytest.fixture(scope="module")
def menu(anomaly_data):
    """{model: (artifact, fitted model)} for every model of ``ALL_MODELS``,
    fitted once."""
    xtr, ytr, _, _ = anomaly_data
    return {m: _fit_model(m, xtr, ytr) for m in ALL_MODELS}


@pytest.fixture(scope="module")
def wide_backends(anomaly_data):
    """{switch_features: backend}: a random forest on the rows the server
    receives, the 5 switch columns alone (None) or 12-column rows holding
    them at ``COLS`` among seeded noise, every column a candidate at every
    split."""
    xtr, ytr, _, _ = anomaly_data
    out = {}
    for sf, rows in ((None, np.asarray(xtr)), (COLS, _wide_rows(xtr, 1))):
        ens = fit_random_forest(rows, ytr, n_classes=2, n_trees=8,
                                max_depth=6, seed=2,
                                max_features=rows.shape[1])
        out[sf] = lambda r, ens=ens: predict_tree_ensemble(ens, r)
    return out


# ---------------------------------------------------------------------------
# every switch model, on both paths, with and without switch_features
# ---------------------------------------------------------------------------

N_ROWS, CAPACITY = 600, 256


def _median_tau(conf):
    """The batch's median switch confidence; where no row lies below it (a
    one-tree DT votes with confidence 1 on every row), the next float up,
    so every row is forwarded and the capacity leaves the switch the rest."""
    tau = np.float32(np.median(conf))
    if not (conf < tau).any():
        tau = np.nextafter(tau, np.float32(np.inf))
    return float(tau)


@pytest.mark.parametrize("switch_features", [None, COLS],
                         ids=["own_width", "switch_features"])
@pytest.mark.parametrize("path", ["fused", "two_phase"])
@pytest.mark.parametrize("model", ALL_MODELS)
def test_every_switch_model_serves_like_hybrid_serve(
        menu, wide_backends, anomaly_data, model, path, switch_features):
    """The server's answers are ``hybrid_serve``'s bit for bit at the
    batch's median confidence, the switch and the backend each answering
    rows, and ``backend_rows`` counts the forwarded rows up to the
    capacity."""
    art, _ = menu[model]
    x5 = np.asarray(anomaly_data[2][:N_ROWS], np.float32)
    x = x5 if switch_features is None else _wide_rows(x5)
    backend = wide_backends[switch_features]
    sw_pred, conf = (np.asarray(a) for a in table_predict(art, x5))
    tau = _median_tau(conf)
    srv = HybridServer(art, backend, threshold=tau, capacity=CAPACITY,
                       fuse=False if path == "two_phase" else None,
                       switch_features=switch_features)
    pred, stats = srv.classify(x)
    assert srv._fused_ok is (path == "fused")
    want, frac_fwd = hybrid_serve(art, backend, x, tau, CAPACITY,
                                  switch_features=switch_features)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(want))
    assert (np.asarray(want) != sw_pred).any()      # the backend's answers count
    n_fwd = int((conf < tau).sum())
    assert n_fwd > 0                    # the switch keeps N_ROWS - CAPACITY
    assert stats.backend_rows == min(n_fwd, CAPACITY)
    # float32 means of the same mask, one of them subtracted from 1
    assert stats.fraction_handled == pytest.approx(1.0 - float(frac_fwd),
                                                   abs=1e-6)


# ---------------------------------------------------------------------------
# every backend kind over the dispatch buffer's row blocks
# ---------------------------------------------------------------------------

BACKENDS = {
    "rf": ("RF", predict_tree_ensemble),
    "xgb": ("XGB", predict_tree_ensemble),
    "iforest": ("IForest", predict_tree_ensemble),     # score > 0.5
    "svm": ("SVM", predict_svm),
    "nb": ("Bayes", predict_nb),
    "kmeans": ("KMeans", predict_kmeans),
}
TAU = 0.9


@pytest.fixture(scope="module")
def block_servers(menu):
    """(backend, capacity) -> (server, shapes the server traced the backend
    at, the backend itself), built on first use: the RF switch in front of
    each backend kind."""
    servers = {}

    def get(name, cap):
        if (name, cap) not in servers:
            model, fn = BACKENDS[name]
            fitted = menu[model][1]
            shapes = []

            def backend(rows):
                return fn(fitted, rows)

            def traced(rows):
                shapes.append(rows.shape)
                return backend(rows)

            srv = HybridServer(menu["RF"][0], traced, threshold=TAU,
                               capacity=cap)
            servers[name, cap] = srv, shapes, backend
        return servers[name, cap]

    return get


@pytest.mark.parametrize("k", ["none", "one", "capacity"])
@pytest.mark.parametrize("cap", [256, 200])
@pytest.mark.parametrize("backend", list(BACKENDS))
def test_every_backend_kind_over_the_blocks(block_servers, menu,
                                            anomaly_data, backend, cap, k):
    """With k rows forwarded, the fused step runs the backend, traced once
    at (B, F), on the ``ceil(k / B)`` blocks that hold them (B = 128 of a
    256-row buffer, the whole of a 200-row one) and answers as
    ``hybrid_serve``'s whole-buffer evaluation bit for bit."""
    k = {"none": 0, "one": 1, "capacity": cap}[k]
    srv, shapes, backend_fn = block_servers(backend, cap)
    b = backend_block(cap)
    art = menu["RF"][0]
    x = _rows_forwarding(art, anomaly_data[2], cap + 64, k, TAU)
    pred, stats = srv.classify(x)
    assert srv._fused_ok and set(shapes) == {(b, x.shape[1])}
    want, _ = hybrid_serve(art, backend_fn, x, TAU, cap)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(want))
    assert stats.backend_rows == k
    assert stats.backend_blocks == -(-k // b)
