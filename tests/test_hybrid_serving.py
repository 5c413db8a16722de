"""Hybrid tier + serving engine integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hybrid import hybrid_predict, hybrid_serve
from repro.core.inference import table_predict
from repro.core.mapping import map_tree_ensemble
from repro.ml.metrics import accuracy
from repro.ml.trees import fit_random_forest, predict_tree_ensemble
from repro.serving.hybrid_serving import HybridServer


@pytest.fixture(scope="module")
def hybrid_setup(request):
    from repro.data.unsw_like import make_unsw_like, train_test_split
    x, y = make_unsw_like(6000, seed=0, n_features=5)
    xtr, ytr, xte, yte = train_test_split(x, y)
    small = fit_random_forest(xtr, ytr, n_classes=2, n_trees=6, max_depth=4,
                              seed=0)
    big = fit_random_forest(xtr, ytr, n_classes=2, n_trees=30, max_depth=6,
                            seed=1, max_features=5)
    art = map_tree_ensemble(small, 5)
    return art, small, big, xte, yte


def test_hybrid_improves_over_switch_alone(hybrid_setup):
    art, small, big, xte, yte = hybrid_setup
    sw_pred, _ = table_predict(art, xte)
    res = hybrid_predict(art, lambda x: predict_tree_ensemble(big, x),
                         xte, threshold=0.9)
    assert accuracy(yte, res.pred) >= accuracy(yte, sw_pred)


def test_threshold_monotone_fraction(hybrid_setup):
    """Higher tau -> less traffic handled at the switch (Fig 10 trend)."""
    art, _, big, xte, yte = hybrid_setup
    fracs = []
    for tau in (0.5, 0.7, 0.9, 0.99):
        res = hybrid_predict(art, lambda x: predict_tree_ensemble(big, x),
                             xte, threshold=tau)
        fracs.append(float(res.fraction_handled))
    assert all(fracs[i] >= fracs[i + 1] for i in range(len(fracs) - 1))


def test_hybrid_serve_capacity_bound(hybrid_setup):
    art, _, big, xte, yte = hybrid_setup
    seen = []

    def backend(rows):
        seen.append(rows.shape)
        return predict_tree_ensemble(big, rows)

    pred, frac_fwd = hybrid_serve(art, backend, xte[:1024],
                                  threshold=0.95, capacity=128)
    assert seen == [(128, 5)]          # backend saw exactly capacity rows
    assert pred.shape == (1024,)


def test_hybrid_server_update_tables(hybrid_setup):
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, lambda r: predict_tree_ensemble(big, r),
                       threshold=0.7, capacity=256)
    p1, _ = srv.classify(xte[:512])
    # retrain under same constraints -> same shapes -> hot swap
    from repro.data.unsw_like import make_unsw_like
    x2, y2 = make_unsw_like(3000, seed=9, n_features=5)
    small2 = fit_random_forest(x2, y2, n_classes=2, n_trees=6, max_depth=4,
                               seed=0)
    art2 = map_tree_ensemble(small2, 5)
    if all(jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape,
                                        art, art2))):
        srv.update_tables(art2)
        p2, _ = srv.classify(xte[:512])
        assert p2.shape == p1.shape


def test_padded_rows_never_perturb_telemetry(hybrid_setup):
    """Ragged batches run through kernel tile padding (replicated last row,
    never zero rows) and must report exactly the telemetry of the logical
    rows — Figs 10-11 quantities can't drift with batch alignment."""
    art, small, big, xte, yte = hybrid_setup
    tau, cap = 0.9, 64
    srv = HybridServer(art, lambda r: predict_tree_ensemble(big, r),
                       threshold=tau, capacity=cap, use_pallas=True)
    for n in (130, 256, 301):                   # ragged and aligned
        pred, stats = srv.classify(xte[:n])
        _, conf = table_predict(art, xte[:n])
        fwd = np.asarray(conf) < tau
        assert pred.shape == (n,)
        assert stats.fraction_handled == pytest.approx(1.0 - fwd.mean())
        assert stats.backend_rows == min(int(fwd.sum()), cap)


def test_classify_stats_are_lazy_device_arrays(hybrid_setup):
    """classify() returns without host syncs: telemetry stays on device
    until a statistic is actually read."""
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, lambda r: predict_tree_ensemble(big, r),
                       threshold=0.7, capacity=128)
    pred, stats = srv.classify(xte[:256])
    frac, rows = stats.as_arrays()
    assert isinstance(frac, jax.Array) and isinstance(rows, jax.Array)
    assert isinstance(stats.fraction_handled, float)
    assert isinstance(stats.backend_rows, int)
    assert 0.0 <= stats.fraction_handled <= 1.0


def test_hybrid_server_untraceable_backend_falls_back(hybrid_setup):
    """A numpy-only backend can't fuse into the jitted step; the server
    must detect that on first classify and serve via the two-phase path."""
    art, small, big, xte, yte = hybrid_setup

    def np_backend(rows):
        return np.zeros(np.asarray(rows).shape[0], np.int32)

    srv = HybridServer(art, np_backend, threshold=2.0, capacity=32)
    pred, stats = srv.classify(xte[:100])
    assert srv._fused_ok is False
    assert pred.shape == (100,)
    assert stats.backend_rows == 32             # tau=2.0 forwards everything


def test_greedy_generate_deterministic():
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import greedy_generate
    cfg = get_smoke_config("yi-6b")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)}
    o1 = greedy_generate(cfg, params, batch, n_new=6)
    o2 = greedy_generate(cfg, params, batch, n_new=6)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_generate_matches_rerun_prefill():
    """Token t generated with caches == argmax of prefill(prompt+prefix)."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import greedy_generate
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    out = greedy_generate(cfg, params, {"tokens": prompt}, n_new=3,
                          cache_dtype=jnp.float32)
    # recompute token 2 by prefilling prompt + out[:, :2]
    full = jnp.concatenate([prompt, out[:, :2]], axis=1)
    logits, _ = M.prefill(params, cfg, {"tokens": full})
    expect = jnp.argmax(logits, axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, 2]), np.asarray(expect))


def test_servers_resolve_use_pallas_by_platform(hybrid_setup, monkeypatch):
    """use_pallas=None (the default on every server) resolves by platform:
    the XLA references on CPU, the Pallas kernels on a TPU; an explicit
    value is kept."""
    from repro.serving.shard_serving import ShardedStreamingServer
    from repro.serving.stream_serving import StreamingHybridServer
    art, _, big, _, _ = hybrid_setup
    be = lambda x: predict_tree_ensemble(big, x)
    kw = dict(n_buckets=1024, window=128)
    assert HybridServer(art, be).use_pallas is False
    assert StreamingHybridServer(art, be, **kw).use_pallas is False
    assert ShardedStreamingServer(art, be, n_shards=1, **kw).use_pallas \
        is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert HybridServer(art, be).use_pallas is True
    assert HybridServer(art, be, use_pallas=False).use_pallas is False
    assert StreamingHybridServer(art, be, **kw).use_pallas is True
