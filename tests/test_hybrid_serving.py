"""Hybrid tier integration tests: hybrid_serve and HybridServer."""

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


# ---------------------------------------------------------------------------
# spans, scopes and counters of classify
# ---------------------------------------------------------------------------

def _backend(big, path):
    """A traceable backend (the fused step), or one that converts its rows
    to numpy and so runs on the host (the two-phase path)."""
    if path == "fused":
        return lambda r: predict_tree_ensemble(big, r)
    return lambda r: predict_tree_ensemble(big, np.asarray(r))


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_classify_opens_its_spans_in_order(hybrid_setup, monkeypatch, path):
    """Each call opens hybrid.h2d, then hybrid.dispatch (with
    hybrid.backend_host inside it on the two-phase path), all with the
    call's id."""
    import contextlib

    import repro.serving.hybrid_serving as hs
    art, small, big, xte, yte = hybrid_setup
    events = []

    @contextlib.contextmanager
    def span(name, **ids):
        events.append(("open", name, ids))
        yield
        events.append(("close", name, ids))

    monkeypatch.setattr(hs, "span", span)
    srv = HybridServer(art, _backend(big, path), threshold=0.7,
                       capacity=128)
    for _ in range(2):
        srv.classify(xte[:256])
    inner = ["hybrid.backend_host"] if path == "two_phase" else []
    want = []
    for call in range(2):
        ids = {"call": call}
        want += [("open", "hybrid.h2d", ids), ("close", "hybrid.h2d", ids),
                 ("open", "hybrid.dispatch", ids)]
        want += [(kind, n, ids) for n in inner for kind in ("open", "close")]
        want += [("close", "hybrid.dispatch", ids)]
    assert events == want
    assert srv._fused_ok is (path == "fused")


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_classify_counts_calls(hybrid_setup, path):
    """``calls`` counts requests on either path, and the ids it hands the
    spans run 0, 1, 2, ..."""
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, _backend(big, path), threshold=0.7,
                       capacity=128)
    assert srv.calls == 0
    for n in (256, 100, 256):
        srv.classify(xte[:n])
    assert srv.calls == 3
    assert srv._fused_ok is (path == "fused")


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_classify_with_a_depth8_backend_matches_the_heap_walk(
        hybrid_setup, monkeypatch, path):
    """A depth-8 backend forest walked with level-wise selects (inside the
    fused step, or on the host between the two phases) gives the same
    predictions as the same server on the heap walk."""
    from repro.ml import trees
    art, small, big, xte, yte = hybrid_setup
    deep = fit_random_forest(np.asarray(xte), np.asarray(yte), n_classes=2,
                             n_trees=16, max_depth=8, seed=2, max_features=5)
    assert deep.depth == 8 <= trees.SELECT_MAX_DEPTH
    preds = []
    for bound in (trees.SELECT_MAX_DEPTH, 0):
        monkeypatch.setattr(trees, "SELECT_MAX_DEPTH", bound)
        srv = HybridServer(art, _backend(deep, path), threshold=0.95,
                           capacity=256)
        pred, stats = srv.classify(xte[:1000])
        assert srv._fused_ok is (path == "fused")
        assert 0 < stats.backend_rows <= 256
        preds.append(np.asarray(pred))
    np.testing.assert_array_equal(preds[0], preds[1])


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[4]{0}}

%region_0.1 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = s32[] parameter(1)
  ROOT %add.2 = s32[] add(%a, %b), metadata={op_name="jit(step)/switch/reduce_sum" stack_frame_id=20}
}

%wrapped_computation (p: s32[8]) -> s32[] {
  %p = s32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  ROOT %reduce.3 = s32[] reduce(%p, %zero), dimensions={0}, to_apply=%region_0.1
}

%fused_computation.9 (p0: f32[8], p1: s32[4]) -> f32[4] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[4]{0} parameter(1)
  %g.1 = f32[4]{0} gather(%p0, %p1), metadata={op_name="jit(step)/backend/vmap()/gather"}
  %g.2 = f32[4]{0} gather(%p0, %p1), metadata={op_name="jit(step)/backend/gather"}
  ROOT %c.3 = f32[4]{0} add(%g.1, %g.2), metadata={op_name="jit(step)/combine/add"}
}

ENTRY %main.5 (x: f32[8]) -> f32[4] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %sort.1 = s32[8]{0} sort(%x), metadata={op_name="jit(step)/dispatch/jit(argsort)/sort"}
  %fusion.9 = f32[4]{0} fusion(%x, %sort.1), kind=kLoop, calls=%fused_computation.9
  %wrapped_reduce = s32[] fusion(%sort.1), kind=kLoop, calls=%wrapped_computation
  %mul.4 = f32[] multiply(%x, %x), metadata={op_name="jit(step)/mul"}
  ROOT %tuple.7 = (f32[4]{0}) tuple(%fusion.9)
}
"""


def test_op_scopes_reads_optimized_hlo_text():
    """Names keep their .N; the scope is the element after jit(step)/; a
    fusion without its own scope takes the majority of its calls=
    computation, through to_apply= where that computation has none; a bare
    op ran under no scope."""
    from repro.obs import op_scopes
    assert op_scopes(HLO) == {
        "add.2": "switch", "g.1": "backend", "g.2": "backend",
        "c.3": "combine", "sort.1": "dispatch", "fusion.9": "backend",
        "reduce.3": "switch", "wrapped_reduce": "switch"}


def test_step_scopes_of_a_compiled_cpu_step(hybrid_setup):
    """The fused step compiled on the CPU: every scope of the step appears,
    an instruction with its own op_name takes the element after jit(step)/,
    and a fusion without one (XLA's wrapped reduce-windows) takes a scope
    from the computations it calls."""
    import re
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, _backend(big, "fused"), threshold=0.7,
                       capacity=128)
    scopes = srv.step_scopes(256)
    assert set(scopes.values()) == {"switch", "dispatch", "backend",
                                    "combine"}
    text = srv._step.lower(srv.artifact,
                           jax.ShapeDtypeStruct((256, 5), jnp.float32),
                           jnp.float32(0.7)).compile().as_text()
    borrowed = 0
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", line)
        if m is None:
            continue
        name, rest = m.groups()
        own = re.search(r'op_name="jit\(step\)/(\w+)/', rest)
        if own:
            assert scopes[name] == own.group(1), line
        elif " fusion(" in rest and name in scopes:
            borrowed += 1
    assert borrowed > 0
    assert any(re.search(r"\.\d+$", n) for n in scopes)


# ---------------------------------------------------------------------------
# switch_features: the switch parses a few columns of a wider row
# ---------------------------------------------------------------------------

WIDE = 12
COLS = (7, 2, 11, 4, 0)              # the artifact's features 0..4, in order


def _wide_rows(x5, seed=0):
    """(N, WIDE) rows holding the 5 switch features at ``COLS`` and noise
    elsewhere."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(len(x5), WIDE)).astype(np.float32)
    x[:, list(COLS)] = np.asarray(x5, np.float32)
    return x


def _wide_backend(path):
    """A backend that reads columns the switch never parses: 1 where
    column 1 exceeds column 3, fused or on the host."""
    if path == "fused":
        return lambda r: (r[:, 1] > r[:, 3]).astype(jnp.int32)
    return lambda r: np.asarray(np.asarray(r)[:, 1] > np.asarray(r)[:, 3],
                                np.int32)


def _wide_reference(art, x, tau, cap):
    """numpy: the switch's answer on the switch columns; the first ``cap``
    rows below ``tau`` take the backend's answer on the whole row."""
    sw, conf = (np.asarray(a) for a in table_predict(art, x[:, list(COLS)]))
    fwd = conf < tau
    sent = fwd & (np.cumsum(fwd) <= cap)
    return np.where(sent, (x[:, 1] > x[:, 3]).astype(np.int32), sw), sent


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_switch_features_match_a_numpy_reference(hybrid_setup, path):
    """The switch classifies the columns ``switch_features`` names and the
    backend answers on the whole forwarded rows, on either path; the
    dense and serving forms of ``core.hybrid`` agree."""
    from repro.core.hybrid import switch_columns
    art, small, big, xte, yte = hybrid_setup
    x = _wide_rows(xte[:700])
    tau, cap = 0.95, 64
    srv = HybridServer(art, _wide_backend(path), threshold=tau,
                       capacity=cap, switch_features=COLS)
    pred, stats = srv.classify(x)
    assert srv._fused_ok is (path == "fused")
    want, sent = _wide_reference(art, x, tau, cap)
    assert 0 < sent.sum() == stats.backend_rows <= cap
    assert (want != np.asarray(table_predict(art, x[:, list(COLS)])[0])
            ).any()                     # the backend's answers count
    np.testing.assert_array_equal(np.asarray(pred), want)
    np.testing.assert_array_equal(np.asarray(switch_columns(x, COLS)),
                                  x[:, list(COLS)])
    served, _ = hybrid_serve(art, _wide_backend("fused"), x, tau, cap,
                             switch_features=COLS)
    np.testing.assert_array_equal(np.asarray(served), want)
    dense = hybrid_predict(art, _wide_backend("fused"), x, tau,
                           switch_features=COLS)
    np.testing.assert_array_equal(
        np.asarray(dense.pred),
        np.where(np.asarray(dense.handled), np.asarray(dense.switch_pred),
                 (x[:, 1] > x[:, 3]).astype(np.int32)))


def test_switch_features_none_is_the_server_without_them(hybrid_setup):
    """``switch_features=None`` lowers to the very step of a server built
    without the keyword, one with no column slicing in it; naming every
    column in order gives the same answers through a step that slices."""
    art, small, big, xte, yte = hybrid_setup
    be = _backend(big, "fused")
    x = jax.ShapeDtypeStruct((256, 5), jnp.float32)
    tau = jnp.float32(0.7)
    servers = [HybridServer(art, be, capacity=128, **kw)
               for kw in ({}, {"switch_features": None},
                          {"switch_features": range(5)})]
    texts = [s._step.lower(s.artifact, x, tau).as_text() for s in servers]
    assert texts[0] == texts[1]
    assert "concatenate" in texts[2] and texts[2] != texts[0]
    preds = [np.asarray(s.classify(xte[:256])[0]) for s in servers]
    np.testing.assert_array_equal(preds[0], preds[1])
    np.testing.assert_array_equal(preds[0], preds[2])


def test_switch_features_must_match_the_artifact(hybrid_setup):
    art, small, big, xte, yte = hybrid_setup
    with pytest.raises(ValueError, match="switch_features names 4"):
        HybridServer(art, _backend(big, "fused"), switch_features=(0, 1, 2, 3))


def test_step_scopes_of_a_wide_step(hybrid_setup):
    """A server with ``switch_features`` maps the step compiled for its
    request width: the column slice under ``switch``, the backend's reads
    of the unparsed columns under ``backend``."""
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, _wide_backend("fused"), threshold=0.7,
                       capacity=128, switch_features=COLS)
    scopes = srv.step_scopes(256, WIDE)
    assert set(scopes.values()) == {"switch", "dispatch", "backend",
                                    "combine"}


# ---------------------------------------------------------------------------
# the backend runs only on the dispatch buffer's blocks of forwarded rows
# ---------------------------------------------------------------------------

def _rows_forwarding(art, xte, n, k, tau, seed=0):
    """n rows of ``xte`` in a seeded order, exactly k of them below ``tau``
    at the switch (rows repeat where ``xte`` has too few)."""
    conf = np.asarray(table_predict(art, xte)[1])
    low, high = np.flatnonzero(conf < tau), np.flatnonzero(conf >= tau)
    idx = np.concatenate([np.resize(low, k), np.resize(high, n - k)])
    return np.asarray(xte)[np.random.default_rng(seed).permutation(idx)]


@pytest.mark.parametrize("cap,k", [
    (256, 0), (256, 1), (256, 127), (256, 128), (256, 129), (256, 256),
    (1024, 0), (1024, 1), (1024, 127), (1024, 128), (1024, 129),
    (1024, 1024), (200, 0), (200, 1), (200, 200)])
def test_backend_runs_on_the_filled_blocks_only(hybrid_setup, cap, k):
    """With k rows forwarded to a ``cap``-row buffer, the fused step traces
    the backend once per block shape, (128, F) or (cap, F) where ``cap`` is
    no multiple of 128, and answers as ``hybrid_serve``'s whole-buffer
    evaluation bit for bit; ``backend_over_blocks`` leaves the blocks past
    the forwarded rows unevaluated, and ``backend_blocks`` counts the
    blocks that ran."""
    from repro.core.hybrid import backend_over_blocks, dispatch
    art, small, big, xte, yte = hybrid_setup
    tau = 0.9
    b = 128 if cap % 128 == 0 else cap
    x = _rows_forwarding(art, xte, cap + 64, k, tau)
    shapes = []

    def backend(rows):
        # a class per row that the switch never answers, so each backend
        # answer shows where combine put it
        shapes.append(rows.shape)
        return 2 + (jnp.abs(rows[:, 0] * 1e3).astype(jnp.int32) % 5)

    srv = HybridServer(art, backend, threshold=tau, capacity=cap)
    pred, stats = srv.classify(x)
    assert srv._fused_ok and set(shapes) == {(b, 5)}
    want, _ = hybrid_serve(art, backend, x, tau, cap)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(want))
    assert int((np.asarray(pred) >= 2).sum()) == k
    assert stats.backend_rows == int(stats.as_arrays()[1]) == k
    assert stats.backend_blocks == -(-k // b) <= cap // b
    if k == 0:
        sw, _ = table_predict(art, x)
        np.testing.assert_array_equal(np.asarray(pred), np.asarray(sw))

    fwd = np.asarray(table_predict(art, x)[1]) < tau
    buf, _, _ = dispatch(jnp.asarray(x), jnp.asarray(fwd), cap)
    ans = np.asarray(jax.jit(lambda r, n: backend_over_blocks(
        backend, r, n))(buf, jnp.int32(k)))
    ran = -(-k // b) * b
    np.testing.assert_array_equal(ans[:ran], np.asarray(backend(buf))[:ran])
    assert not ans[ran:].any()


# ---------------------------------------------------------------------------
# tau: one device scalar per threshold, none per call
# ---------------------------------------------------------------------------

def _device_backend(big):
    """A backend that runs on the device (jitted), so the two-phase path
    moves nothing to the device either; ``fuse=False`` forces that path."""
    return jax.jit(lambda r: predict_tree_ensemble(big, r))


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_classify_of_rows_on_the_device_transfers_nothing(hybrid_setup, path):
    """Once compiled, a call whose rows are already on the device makes no
    host-to-device transfer on either path: tau is the server's device
    scalar, not a value rebuilt per call."""
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, _device_backend(big), threshold=0.9,
                       capacity=128,
                       fuse=None if path == "fused" else False)
    x = jax.device_put(np.asarray(xte[:256], np.float32))
    want_pred, want_stats = srv.classify(x)
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(2):
            pred, stats = srv.classify(x)
            jax.block_until_ready((pred, stats.as_arrays()))
    assert srv._fused_ok is (path == "fused")
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(want_pred))
    assert stats.backend_rows == want_stats.backend_rows > 0


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_a_new_threshold_serves_like_a_fresh_server(hybrid_setup, path):
    """Setting ``threshold`` between calls gives the predictions and stats
    of a server built at that tau, and the step is traced and compiled
    once across the whole sweep."""
    art, small, big, xte, yte = hybrid_setup
    traced = []

    def backend(rows):
        traced.append(rows.shape)
        return predict_tree_ensemble(big, rows)

    kw = dict(capacity=128, fuse=None if path == "fused" else False)
    srv = HybridServer(art, backend, threshold=0.7, **kw)
    x = xte[:512]
    rows, n_traced = set(), []
    for tau in (0.7, 0.9, 0.55, 0.99, 0.7):
        srv.threshold = tau
        pred, stats = srv.classify(x)
        n_traced.append(len(traced))
        fresh_pred, fresh = HybridServer(art, _backend(big, "fused"),
                                         threshold=tau, **kw).classify(x)
        np.testing.assert_array_equal(np.asarray(pred),
                                      np.asarray(fresh_pred))
        assert stats.fraction_handled == fresh.fraction_handled
        assert stats.backend_rows == fresh.backend_rows
        rows.add(stats.backend_rows)
    assert len(rows) > 2                        # the sweep moved the split
    jitted = srv._step if path == "fused" else srv._switch_only
    assert jitted._cache_size() == 1
    if path == "fused":                         # (two-phase: runs eagerly)
        assert len(set(n_traced)) == 1          # traced by the first call only


def test_threshold_uploads_counts_the_changes_of_threshold(hybrid_setup):
    """One device scalar per new threshold: none per call, none for
    setting the value the server already holds."""
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, _backend(big, "fused"), threshold=0.7,
                       capacity=128)
    assert srv.threshold_uploads == 1
    for _ in range(3):
        srv.classify(xte[:256])
    assert (srv.calls, srv.threshold_uploads) == (3, 1)
    srv.threshold = 0.7
    assert srv.threshold_uploads == 1
    srv.threshold = 0.9
    srv.classify(xte[:256])
    assert (srv.threshold, srv.threshold_uploads) == (0.9, 2)
    srv.threshold = 0.7
    assert srv.threshold_uploads == 3
    assert srv._tau.dtype == jnp.float32 and srv._tau.shape == ()
    assert float(srv._tau) == np.float32(0.7)


def test_the_step_lowers_as_with_a_tau_made_per_call(hybrid_setup):
    """The scalar ``classify`` passes is a traced f32[]: the step lowers
    to the same text, hash for hash, as with ``jnp.float32(tau)`` made per
    call and as with an abstract f32[] argument."""
    import hashlib
    art, small, big, xte, yte = hybrid_setup
    srv = HybridServer(art, _backend(big, "fused"), threshold=0.7,
                       capacity=128)
    x = jax.ShapeDtypeStruct((256, 5), jnp.float32)
    hashes = {hashlib.sha256(srv._step.lower(srv.artifact, x, tau)
                             .as_text().encode()).hexdigest()
              for tau in (srv._tau, jnp.float32(0.7),
                          jax.ShapeDtypeStruct((), jnp.float32))}
    assert len(hashes) == 1


def _as_node_arrays(ens):
    """A complete heap forest as node arrays: node ``i``'s children
    ``2i+1`` and ``2i+2`` held in ``left``, each leaf its own index. It
    predicts what the heap predicts, through the node walk."""
    from repro.ml.trees import TreeEnsemble
    n_trees, h = ens.feat.shape
    m = 2 * h + 1
    node = jnp.arange(m, dtype=jnp.int32)
    pad = (n_trees, h + 1)
    return TreeEnsemble(
        feat=jnp.concatenate([ens.feat, jnp.zeros(pad, jnp.int32)], 1),
        thresh=jnp.concatenate([ens.thresh, jnp.full(pad, jnp.inf)], 1),
        leaf=jnp.concatenate([jnp.zeros((n_trees, h, ens.leaf.shape[2])),
                              ens.leaf], 1),
        kind=ens.kind, n_classes=ens.n_classes,
        left=jnp.broadcast_to(jnp.where(node < h, 2 * node + 1, node),
                              (n_trees, m)),
        depth=ens.depth)


@pytest.mark.parametrize("path", ["fused", "two_phase"])
def test_a_node_array_backend_serves_as_hybrid_serve(hybrid_setup, path):
    """A backend over node arrays, passed as a pytree
    (``Partial(predict_tree_ensemble, ens)``), serves bit for bit what
    ``core.hybrid.hybrid_serve`` answers with the same backend, and what a
    server over the same forest as a complete heap (a closure) answers."""
    from jax.tree_util import Partial
    art, small, big, xte, yte = hybrid_setup
    nodes = _as_node_arrays(big)
    assert not nodes.complete
    backend = Partial(predict_tree_ensemble, nodes)
    srv = HybridServer(art, backend, threshold=0.95, capacity=256,
                       fuse=None if path == "fused" else False)
    heap = HybridServer(art, lambda r: predict_tree_ensemble(big, r),
                        threshold=0.95, capacity=256)
    x = xte[:1000]
    pred, stats = srv.classify(x)
    assert srv._fused_ok is (path == "fused")
    assert 0 < stats.backend_rows <= 256
    want, _ = hybrid_serve(art, backend, x, 0.95, 256)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(pred),
                                  np.asarray(heap.classify(x)[0]))


def test_a_pytree_backend_is_put_on_the_device_once(hybrid_setup,
                                                     monkeypatch):
    """The backend's arrays go to the device at construction, inside the
    one ``hybrid.backend_put`` span, and every call passes those device
    arrays to the step; a plain closure has none and opens no span."""
    import contextlib

    from jax.tree_util import Partial

    import repro.serving.hybrid_serving as hs
    art, small, big, xte, yte = hybrid_setup
    events = []

    @contextlib.contextmanager
    def span(name, **ids):
        events.append(name)
        yield

    monkeypatch.setattr(hs, "span", span)
    nodes = _as_node_arrays(big)
    host = jax.tree.map(np.asarray, nodes)
    srv = HybridServer(art, Partial(predict_tree_ensemble, host),
                       capacity=128)
    assert events == ["hybrid.backend_put"]
    leaves = jax.tree.leaves(srv._backend)
    assert len(leaves) == len(jax.tree.leaves(nodes)) == 4
    assert all(isinstance(w, jax.Array) for w in leaves)
    for _ in range(2):
        srv.classify(xte[:256])
    assert events.count("hybrid.backend_put") == 1
    events.clear()
    plain = HybridServer(art, lambda r: predict_tree_ensemble(big, r),
                         capacity=128)
    assert events == [] and jax.tree.leaves(plain._backend) == []


def test_a_plain_backend_lowers_as_when_closed_over(hybrid_setup):
    """A plain function goes to the step as a ``Partial`` with no leaves:
    passed as the step's argument it lowers to the very text of the step
    that closes over it (the default), and it keeps its arrays as
    constants; a ``Partial`` backend is passed as it is, not wrapped."""
    from jax.tree_util import Partial
    art, small, big, xte, yte = hybrid_setup
    x = jax.ShapeDtypeStruct((256, 5), jnp.float32)
    tau = jnp.float32(0.7)
    srv = HybridServer(art, _backend(big, "fused"), capacity=128)
    assert isinstance(srv._backend, Partial)
    assert srv._backend.func is srv.backend_fn
    closed = srv._step.lower(srv.artifact, x, tau).as_text()
    passed = srv._step.lower(srv.artifact, x, tau, srv._backend).as_text()
    assert passed == closed
    nodes = Partial(predict_tree_ensemble, _as_node_arrays(big))
    tree = HybridServer(art, nodes, capacity=128)
    assert tree._backend.func is predict_tree_ensemble
    assert (jax.tree.structure(tree._backend)
            == jax.tree.structure(nodes))


def test_the_step_holds_no_constant_of_the_forest(hybrid_setup):
    """The lowered step of a server over node arrays takes the forest as
    arguments: its text does not grow with the node count (depth 4 to 8,
    16 times the nodes), where a closure over the same forests lowers
    them into the step as constants."""
    from jax.tree_util import Partial
    art, small, big, xte, yte = hybrid_setup
    x = jax.ShapeDtypeStruct((256, 5), jnp.float32)
    tau = jnp.float32(0.7)
    forests = [_as_node_arrays(fit_random_forest(
        xte, yte, n_classes=2, n_trees=30, max_depth=d, seed=1))
        for d in (4, 8)]
    sizes = [sum(a.nbytes for a in jax.tree.leaves(f)) for f in forests]
    assert sizes[1] > 10 * sizes[0]

    def text(backend):
        srv = HybridServer(art, backend, capacity=128)
        return srv._step.lower(srv.artifact, x, tau,
                               srv._backend).as_text()

    args = [text(Partial(predict_tree_ensemble, f)) for f in forests]
    consts = [text(lambda r, f=f: predict_tree_ensemble(f, r))
              for f in forests]
    assert abs(len(args[1]) - len(args[0])) < 64
    assert len(consts[1]) - len(consts[0]) > sizes[1] - sizes[0]
    assert len(args[1]) < len(consts[1]) - sizes[1]
