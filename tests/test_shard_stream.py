"""Sharded flow-table subsystem: multi-device streaming contracts.

The contracts under test (DESIGN.md §6):

* sharded streaming (shard_map over the 'shard' mesh) is bit-identical
  to the batch flow table AND to the single-device StreamingHybridServer
  on in-order traces with eviction disabled, at every mesh size;
* the aging sweep recycles idle buckets to the init identities — an
  evicted-then-reborn flow is indistinguishable from a fresh one — and
  is a bitwise no-op on surviving buckets;
* the 2^24 overflow guard saturates count registers and counts the hits;
* the stream epoch is a min-merged register, so an out-of-order start
  (reordered first window) is tolerated without a host-side min latch.

Runs on whatever devices exist: mesh sizes are the divisors of
``jax.device_count()`` capped at 4 — a plain single-device session
exercises the D=1 shard_map path; the CI multi-device step
(XLA_FLAGS=--xla_force_host_platform_device_count=4) exercises 1/2/4.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mapping import map_tree_ensemble
from repro.ml.trees import fit_random_forest, predict_tree_ensemble
from repro.netsim.features import flow_features
from repro.netsim.packets import synth_trace
from repro.netsim.shard_stream import (init_sharded_table,
                                       stream_sharded_flow_features)
from repro.netsim.stream import (OVERFLOW_LIMIT, PacketWindow, age_out,
                                 flow_table_readout, init_flow_table,
                                 iter_windows, saturate_counts,
                                 update_flow_table)
from repro.serving.shard_serving import ShardedStreamingServer
from repro.serving.stream_serving import StreamingHybridServer

N_BUCKETS = 1 << 11

DEVICE_COUNTS = [d for d in (1, 2, 4) if jax.device_count() % d == 0
                 and d <= jax.device_count()]


def _reorder_head(trace, n, seed=0):
    """Permute the first n packets in place-order (a reordered opening)."""
    perm = np.arange(trace.n_packets)
    perm[:n] = np.random.default_rng(seed).permutation(n)
    return dataclasses.replace(trace, **{
        f.name: getattr(trace, f.name)[perm]
        for f in dataclasses.fields(trace) if f.name != "flow_label"})


@pytest.fixture(scope="module")
def shard_setup():
    trace = synth_trace(n_flows=300, seed=3)
    b, table = flow_features(trace, n_buckets=N_BUCKETS)
    first_idx = np.unique(np.asarray(trace.flow_id), return_index=True)[1]
    rows = np.asarray(table)[np.asarray(b)[first_idx]].astype(np.float32)
    small = fit_random_forest(rows, trace.flow_label, n_classes=2,
                              n_trees=4, max_depth=3, seed=0)
    big = fit_random_forest(rows, trace.flow_label, n_classes=2,
                            n_trees=12, max_depth=5, seed=1)
    art = map_tree_ensemble(small, rows.shape[1])
    return trace, art, (lambda r: predict_tree_ensemble(big, r))


# ---------------------------------------------------------------------------
# sharded register carry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", DEVICE_COUNTS)
def test_sharded_table_bit_equals_batch(n_shards):
    """shard_map'd window updates over every mesh size reproduce the
    one-shot flow_features table bit for bit (incl. ragged final window)."""
    tr = synth_trace(n_flows=250, seed=5)
    _, batch_table = flow_features(tr, n_buckets=N_BUCKETS)
    _, sh_table = stream_sharded_flow_features(
        tr, n_buckets=N_BUCKETS, window=257, n_shards=n_shards)
    np.testing.assert_array_equal(np.asarray(sh_table),
                                  np.asarray(batch_table))


def test_sharded_table_rejects_indivisible_buckets():
    with pytest.raises(ValueError):
        init_sharded_table(N_BUCKETS + 1, n_shards=2)


# ---------------------------------------------------------------------------
# sharded serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", DEVICE_COUNTS)
def test_sharded_serving_bit_identical_to_single_device(shard_setup,
                                                        n_shards):
    """The acceptance contract: same predictions, same telemetry, same
    flow-table readout as StreamingHybridServer, eviction disabled."""
    trace, art, backend = shard_setup
    kw = dict(n_buckets=N_BUCKETS, window=256, threshold=0.9, capacity=32)
    ref = StreamingHybridServer(art, backend, **kw)
    p_ref, s_ref = ref.serve_trace(trace)
    srv = ShardedStreamingServer(art, backend, n_shards=n_shards, **kw)
    p, s = srv.serve_trace(trace)
    assert srv._fused_ok is True                  # single-dispatch path ran
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    assert s.n_packets == s_ref.n_packets
    assert s.fraction_handled == s_ref.fraction_handled
    assert s.total_backend_rows == s_ref.total_backend_rows
    assert s.n_evicted == 0 and s.n_overflow == 0
    np.testing.assert_array_equal(np.asarray(srv.flow_table()),
                                  np.asarray(ref.flow_table()))
    assert srv.epoch == 0.0                       # in-order stream


def test_sharded_serving_untraceable_backend_falls_back(shard_setup):
    trace, art, _ = shard_setup

    def np_backend(rows):
        return np.zeros(np.asarray(rows).shape[0], np.int32)

    srv = ShardedStreamingServer(art, np_backend, n_buckets=N_BUCKETS,
                                 window=256, threshold=2.0, capacity=16,
                                 n_shards=DEVICE_COUNTS[-1])
    preds, stats = srv.serve_trace(trace)
    assert srv._fused_ok is False
    assert preds.shape == (trace.n_packets,)
    # tau=2.0 forwards everything: every window fills its backend buffer,
    # the overflow past capacity is visible as deferred accounting
    assert stats.total_backend_rows == stats.n_windows * 16
    assert stats.n_deferred == stats.n_packets - stats.total_backend_rows
    np.testing.assert_array_equal(
        np.asarray(srv.flow_table()),
        np.asarray(flow_features(trace, n_buckets=N_BUCKETS)[1]))


# ---------------------------------------------------------------------------
# cross-window deferred dispatch: shard-aware flushes (DESIGN.md §7)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", DEVICE_COUNTS)
def test_sharded_deferred_bit_matches_flush_every_1(shard_setup, n_shards):
    """The sharded deferral contract at every mesh size: per-shard-slice
    flushes (reduce-scattered complete rows, one backend slice per
    shard) return the same final predictions, flow table and accounting
    as the per-window sharded baseline AND the single-device tier, with
    ceil(windows/k) backend invocations."""
    trace, art, backend = shard_setup
    kw = dict(n_buckets=N_BUCKETS, window=256, threshold=0.9, capacity=32)
    single = StreamingHybridServer(art, backend, **kw)
    p_single, _ = single.serve_trace(trace)
    ref = ShardedStreamingServer(art, backend, n_shards=n_shards, **kw)
    p_ref, s_ref = ref.serve_trace(trace)
    srv = ShardedStreamingServer(art, backend, n_shards=n_shards,
                                 flush_every=4, **kw)
    p, s = srv.serve_trace(trace)
    assert srv._fused_ok is True
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_single))
    np.testing.assert_array_equal(np.asarray(srv.flow_table()),
                                  np.asarray(ref.flow_table()))
    assert s.n_packets == s_ref.n_packets
    assert s.fraction_handled == s_ref.fraction_handled
    assert s.total_backend_rows == s_ref.total_backend_rows
    assert s.n_deferred == s_ref.n_deferred
    assert s.n_flushes == -(-s.n_windows // 4)
    assert s_ref.n_flushes == s_ref.n_windows


def test_sharded_deferred_two_phase_bit_identical(shard_setup):
    """Satellite contract: the two-phase fallback of the sharded tier
    under deferral (host backend over the shard-summed buffer) is
    bit-identical to the fused per-shard-slice path and to the
    single-device tier — including a mid-trace backend flush and the
    guaranteed partial flush at trace end."""
    trace, art, backend = shard_setup
    kw = dict(n_buckets=N_BUCKETS, window=256, threshold=0.9, capacity=32,
              flush_every=2)
    fused = ShardedStreamingServer(art, backend,
                                   n_shards=DEVICE_COUNTS[-1], **kw)
    p_f, s_f = fused.serve_trace(trace)
    assert fused._fused_ok is True
    twop = ShardedStreamingServer(art, backend, fuse=False,
                                  n_shards=DEVICE_COUNTS[-1], **kw)
    p_t, s_t = twop.serve_trace(trace)
    assert twop._fused_ok is False
    single = StreamingHybridServer(art, backend, **kw)
    p_s, s_s = single.serve_trace(trace)
    assert s_t.n_windows > 2          # the cycle flushed mid-trace
    assert s_t.n_flushes == -(-s_t.n_windows // 2) >= 2
    np.testing.assert_array_equal(np.asarray(p_t), np.asarray(p_f))
    np.testing.assert_array_equal(np.asarray(p_t), np.asarray(p_s))
    np.testing.assert_array_equal(np.asarray(twop.flow_table()),
                                  np.asarray(single.flow_table()))
    assert s_t.total_backend_rows == s_f.total_backend_rows \
        == s_s.total_backend_rows
    assert s_t.n_flushes == s_f.n_flushes == s_s.n_flushes


def test_sharded_deferred_rejects_indivisible_slots(shard_setup):
    """Under deferral, flush_every*capacity must divide over the mesh
    (each shard's backend serves one slice of the buffer per flush);
    flush_every=1 never builds the buffer, so the same capacity stays
    legal there."""
    if DEVICE_COUNTS[-1] == 1:
        pytest.skip("needs a multi-device mesh")
    trace, art, backend = shard_setup
    with pytest.raises(ValueError):
        ShardedStreamingServer(art, backend, n_buckets=N_BUCKETS,
                               capacity=3, flush_every=3,
                               n_shards=DEVICE_COUNTS[-1])
    srv = ShardedStreamingServer(art, backend, n_buckets=N_BUCKETS,
                                 capacity=3, flush_every=1,
                                 n_shards=DEVICE_COUNTS[-1])
    assert srv.capacity == 3                      # per-window path: legal


# ---------------------------------------------------------------------------
# device-resident chunked streaming (DESIGN.md §8) on the mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", DEVICE_COUNTS)
def test_sharded_chunked_serving_bit_matches_single_device(shard_setup,
                                                           n_shards):
    """The sharded scan megastep (shard_map'd register scan, one readout
    psum per chunk, per-shard backend slices) serves bit-identically to
    the single-device per-window baseline at every mesh size."""
    trace, art, backend = shard_setup
    kw = dict(n_buckets=N_BUCKETS, window=256, threshold=0.9, capacity=32)
    ref = StreamingHybridServer(art, backend, **kw)
    p_ref, s_ref = ref.serve_trace(trace)
    srv = ShardedStreamingServer(art, backend, chunk_windows=4,
                                 n_shards=n_shards, **kw)
    p, s = srv.serve_trace(trace)
    assert srv._fused_ok is True
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    np.testing.assert_array_equal(np.asarray(srv.flow_table()),
                                  np.asarray(ref.flow_table()))
    assert s.n_packets == s_ref.n_packets
    assert s.n_handled == s_ref.n_handled
    assert s.total_backend_rows == s_ref.total_backend_rows
    assert s.n_deferred == s_ref.n_deferred


def test_sharded_chunked_rejects_indivisible_slots(shard_setup):
    """chunk_windows*capacity must divide over the mesh — each shard's
    backend serves one slice of the chunk's deferred rows."""
    if DEVICE_COUNTS[-1] == 1:
        pytest.skip("needs a multi-device mesh")
    trace, art, backend = shard_setup
    with pytest.raises(ValueError):
        ShardedStreamingServer(art, backend, n_buckets=N_BUCKETS,
                               capacity=3, chunk_windows=3,
                               n_shards=DEVICE_COUNTS[-1])


# ---------------------------------------------------------------------------
# eviction / aging
# ---------------------------------------------------------------------------

def _one_flow_state(ts_list, n_buckets=64, bucket=7, length=100.0):
    """Fold packets of a single flow (given rebased ts) into a fresh table."""
    state = init_flow_table(n_buckets)
    n = len(ts_list)
    win = PacketWindow(
        bucket=jnp.full((n,), bucket, jnp.int32),
        ts=jnp.asarray(ts_list, jnp.float32),
        length=jnp.full((n,), length, jnp.float32),
        is_fwd=jnp.ones((n,), jnp.float32),
        valid=jnp.ones((n,), bool))
    return update_flow_table(state, win)


def test_evicted_then_reborn_flow_matches_fresh():
    """Eviction resets a bucket to the init identities: a flow reborn in
    an evicted bucket reads out bit-for-bit like a fresh flow."""
    old = _one_flow_state([0.5, 1.0, 1.5])
    evicted, n_ev = age_out(old, 10.0)            # cutoff after last-seen
    assert int(n_ev) == 1
    reborn = _one_flow_state([20.0, 21.0])        # same bucket, new life
    win = PacketWindow(bucket=jnp.full((2,), 7, jnp.int32),
                       ts=jnp.asarray([20.0, 21.0], jnp.float32),
                       length=jnp.full((2,), 100.0, jnp.float32),
                       is_fwd=jnp.ones((2,), jnp.float32),
                       valid=jnp.ones((2,), bool))
    reborn_after_evict = update_flow_table(evicted, win)
    np.testing.assert_array_equal(
        np.asarray(flow_table_readout(reborn_after_evict)),
        np.asarray(flow_table_readout(reborn)))


def test_aging_sweep_noop_on_survivors():
    """A sweep on an idle table leaves surviving buckets bit-unchanged
    and resets only the stale ones."""
    tr = synth_trace(n_flows=100, seed=11)
    state = init_flow_table(512)
    for w in iter_windows(tr, 4096, 512):
        state = update_flow_table(state, w)
    cutoff = 0.0                                  # before every packet
    swept, n_ev = age_out(state, cutoff)
    assert int(n_ev) == 0                         # nothing predates t=0
    np.testing.assert_array_equal(np.asarray(flow_table_readout(swept)),
                                  np.asarray(flow_table_readout(state)))
    # now a cutoff that splits: early flows evicted, late flows untouched
    mid = float(np.median(np.asarray(state.t_max)[
        np.asarray(state.pkt_count) > 0]))
    swept, n_ev = age_out(state, mid)
    survivors = np.asarray((state.pkt_count > 0) & (state.t_max >= mid))
    assert 0 < int(n_ev) < int(np.sum(np.asarray(state.pkt_count) > 0))
    for f in ("pkt_count", "byte_count", "t_min", "t_max"):
        np.testing.assert_array_equal(
            np.asarray(getattr(swept, f))[survivors],
            np.asarray(getattr(state, f))[survivors])
    evicted_rows = np.asarray(flow_table_readout(swept))[
        np.asarray((state.pkt_count > 0) & (state.t_max < mid))]
    np.testing.assert_array_equal(evicted_rows,
                                  np.zeros_like(evicted_rows))


def test_lifecycle_sweep_cutoff_clamped_to_window_min():
    """A window whose time span exceeds evict_age must not evict flows
    seen in (or alive at the start of) that window: the cutoff clamps to
    the window's oldest timestamp, so only buckets idle since *before*
    this window can be recycled."""
    from repro.netsim.stream import lifecycle_sweep
    state = _one_flow_state([0.2])                # last seen at t=0.2
    # window spans [0.1, 10.0]: now - evict_age = 9.5 would evict t=0.2,
    # but the clamp to window-min 0.1 keeps it alive
    win = PacketWindow(bucket=jnp.full((2,), 9, jnp.int32),
                       ts=jnp.asarray([0.1, 10.0], jnp.float32),
                       length=jnp.full((2,), 10.0, jnp.float32),
                       is_fwd=jnp.ones((2,), jnp.float32),
                       valid=jnp.ones((2,), bool))
    state = update_flow_table(state, win)
    swept, n_ev, _ = lifecycle_sweep(state, win, 0.5, True)
    assert int(n_ev) == 0
    np.testing.assert_array_equal(np.asarray(flow_table_readout(swept)),
                                  np.asarray(flow_table_readout(state)))
    # a bucket idle since before the window IS evicted by the same sweep
    stale = _one_flow_state([0.05])               # predates window-min
    stale = update_flow_table(stale, win)
    _, n_ev, _ = lifecycle_sweep(stale, win, 0.01, True)
    assert int(n_ev) == 1


def test_sharded_eviction_recycles_buckets(shard_setup):
    """End-to-end: an aggressive evict_age recycles buckets and reports
    them in StreamStats; serving still completes."""
    trace, art, backend = shard_setup
    srv = ShardedStreamingServer(art, backend, n_buckets=N_BUCKETS,
                                 window=256, threshold=0.9, capacity=32,
                                 n_shards=DEVICE_COUNTS[-1], evict_age=0.5)
    preds, stats = srv.serve_trace(trace)
    assert preds.shape == (trace.n_packets,)
    assert stats.n_evicted > 0


# ---------------------------------------------------------------------------
# overflow guard
# ---------------------------------------------------------------------------

def test_overflow_guard_saturates_and_counts():
    state = init_flow_table(32)
    near = OVERFLOW_LIMIT - 2.0
    state = dataclasses.replace(
        state,
        pkt_count=state.pkt_count.at[3].set(near),
        byte_count=state.byte_count.at[3].set(OVERFLOW_LIMIT + 512.0))
    out, n_over = saturate_counts(state)
    assert int(n_over) == 1                       # only byte_count tripped
    assert float(out.byte_count[3]) == OVERFLOW_LIMIT
    assert float(out.pkt_count[3]) == near        # below the limit: exact
    # idempotent on an already-clamped table — and NOT re-counted: the
    # guard reports newly saturated slots, so cumulative telemetry stays
    # constant once a slot sits at the limit (it used to inflate linearly)
    out2, n_over2 = saturate_counts(out)
    assert int(n_over2) == 0
    np.testing.assert_array_equal(np.asarray(out2.byte_count),
                                  np.asarray(out.byte_count))
    # with the pre-window registers available, the count is transition-
    # exact: at-the-limit counts iff the slot was below it before
    out3, n_over3 = saturate_counts(out, prev=state)
    assert int(n_over3) == 0                      # state already >= limit
    fresh = init_flow_table(32)
    _, n_over4 = saturate_counts(out, prev=fresh)
    assert int(n_over4) == 1                      # 0 -> limit: newly


def test_overflow_guard_bitwise_noop_in_envelope():
    """The serving default (saturate=True) must not perturb in-envelope
    streams: clamping below 2^24 is the identity."""
    tr = synth_trace(n_flows=150, seed=7)
    state = init_flow_table(1024)
    for w in iter_windows(tr, 2048, 1024):
        state = update_flow_table(state, w)
    out, n_over = saturate_counts(state)
    assert int(n_over) == 0
    for f in ("pkt_count", "byte_count", "fwd_pkts", "rev_pkts",
              "fwd_bytes", "rev_bytes"):
        np.testing.assert_array_equal(np.asarray(getattr(out, f)),
                                      np.asarray(getattr(state, f)))


# ---------------------------------------------------------------------------
# out-of-order tolerance: epoch as a min-merged register
# ---------------------------------------------------------------------------

def test_out_of_order_start_tolerated_under_provisional_t0():
    """A stream whose true start arrives late, rebased against the first
    packet (the provisional latch) instead of the min: registers are
    associative reductions and features epoch-invariant differences, so
    the sharded readout still bit-matches the batch table. Timestamps are
    2^-10-grained so both rebases are exact in f32 and the contract is
    bitwise, not approximate."""
    tr = synth_trace(n_flows=200, seed=13)
    tr.ts = np.round(tr.ts * 1024.0) / 1024.0     # f32-exact grid
    tr = _reorder_head(tr, min(300, tr.n_packets), seed=1)
    assert float(tr.ts[0]) > float(tr.ts.min())   # true min arrives late
    _, batch_table = flow_features(tr, n_buckets=1024)
    t0_prov = float(tr.ts[0])                     # what a switch latches
    _, sh_table = stream_sharded_flow_features(
        tr, n_buckets=1024, window=128,
        n_shards=DEVICE_COUNTS[-1], t0=t0_prov)
    np.testing.assert_array_equal(np.asarray(sh_table),
                                  np.asarray(batch_table))


def test_sharded_server_epoch_min_merges(shard_setup):
    """Server-level: the epoch register converges to the true observed
    minimum even when the provisional t0 missed it."""
    trace, art, backend = shard_setup
    tr = _reorder_head(trace, 300, seed=2)
    t0_prov = float(tr.ts[0])
    srv = ShardedStreamingServer(art, backend, n_buckets=N_BUCKETS,
                                 window=256, threshold=0.9, capacity=32,
                                 n_shards=DEVICE_COUNTS[-1])
    srv.serve_trace(tr, t0=t0_prov)
    expect = np.float32(np.float64(tr.ts.min()) - t0_prov)
    assert srv.epoch == pytest.approx(float(expect), abs=0.0)


# ---------------------------------------------------------------------------
# partitioned classify on the 2D ('shard', 'data') mesh (DESIGN.md §16)
# ---------------------------------------------------------------------------

# every 2D shape the local device count admits, the (2, 2) square first:
# d_shard*d_data devices on a ('shard', 'data') mesh
MESH_SHAPES = [(ds, dd) for ds, dd in
               ((2, 2), (1, 2), (2, 1), (4, 1), (1, 4), (1, 1))
               if ds * dd <= jax.device_count()]

SERVE_KW = dict(n_buckets=N_BUCKETS, window=256, threshold=0.9, capacity=32)


def _assert_matches_single_device(trace, art, backend, srv, ref=None, **kw):
    """The D×data-parallel grid oracle: preds, flow table and the full
    StreamStats accounting (flushes included) bit-match the single-device
    StreamingHybridServer."""
    if ref is None:
        ref = StreamingHybridServer(art, backend, **kw)
    p_ref, s_ref = ref.serve_trace(trace)
    p, s = srv.serve_trace(trace)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    np.testing.assert_array_equal(np.asarray(srv.flow_table()),
                                  np.asarray(ref.flow_table()))
    assert s.n_packets == s_ref.n_packets
    assert s.fraction_handled == s_ref.fraction_handled
    assert s.total_backend_rows == s_ref.total_backend_rows
    assert s.n_deferred == s_ref.n_deferred
    assert s.n_flushes == s_ref.n_flushes
    s.check()
    return s


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_partitioned_classify_bit_identical_on_2d_mesh(shard_setup,
                                                       mesh_shape):
    """Tentpole oracle, per-window path: the lane-partitioned classify
    (reduce-scattered per-device slabs + all-gathered compact pred/conf)
    is bit-identical to the single-device tier at every mesh shape."""
    from repro.distributed.sharding import flow_shard_mesh
    trace, art, backend = shard_setup
    ds, dd = mesh_shape
    srv = ShardedStreamingServer(art, backend, mesh=flow_shard_mesh(ds, dd),
                                 **SERVE_KW)
    assert srv.partition_classify is True         # the default layout
    _assert_matches_single_device(trace, art, backend, srv, **SERVE_KW)
    assert srv._fused_ok is True


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_partitioned_chunked_classify_bit_identical_on_2d_mesh(shard_setup,
                                                               mesh_shape):
    """Tentpole oracle, chunk megastep: the chunk's K*W lanes partition
    into ceil(K*W/D)-row slabs and still bit-match the single-device
    chunked tier."""
    from repro.distributed.sharding import flow_shard_mesh
    trace, art, backend = shard_setup
    ds, dd = mesh_shape
    if (4 * SERVE_KW["capacity"]) % (ds * dd):
        pytest.skip("chunk slots do not divide over this mesh")
    srv = ShardedStreamingServer(art, backend, mesh=flow_shard_mesh(ds, dd),
                                 chunk_windows=4, **SERVE_KW)
    ref = StreamingHybridServer(art, backend, chunk_windows=4, **SERVE_KW)
    _assert_matches_single_device(trace, art, backend, srv, ref=ref)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_classify_rows_per_device_is_padded_ceiling(shard_setup, mesh_shape):
    """Per-device classify work is the padded ceil(K*W/D) slab — NOT the
    full lane width — for both the per-window and the chunked megastep;
    the merge_overhead baseline keeps the full width."""
    from repro.distributed.sharding import flow_shard_mesh
    from repro.kernels.ops import classify_batch_rows
    from repro.kernels.tuning import shard_tiles
    from repro.netsim.shard_stream import lane_slab_rows
    trace, art, backend = shard_setup
    ds, dd = mesh_shape
    mesh = flow_shard_mesh(ds, dd)
    for k in (None, 4):
        if k and (k * SERVE_KW["capacity"]) % (ds * dd):
            continue
        srv = ShardedStreamingServer(art, backend, mesh=mesh,
                                     chunk_windows=k, **SERVE_KW)
        lanes = (k or 1) * SERVE_KW["window"]
        slab = lane_slab_rows(lanes, ds, dd)
        want = classify_batch_rows(art, slab, use_pallas=srv.use_pallas,
                                   tiles=shard_tiles(srv.tiles, slab))
        assert srv.classify_rows_per_device == want
        if ds * dd > 1:
            assert srv.classify_rows_per_device < lanes
        base = ShardedStreamingServer(art, backend, mesh=mesh,
                                      chunk_windows=k,
                                      partition_classify=False, **SERVE_KW)
        assert base.classify_rows_per_device >= lanes


def test_merge_overhead_baseline_bit_identical(shard_setup):
    """partition_classify=False (the pre-partitioning replicated-classify
    layout the bench labels merge_overhead) still bit-matches the
    single-device tier — the flag switches layout, never values."""
    from repro.distributed.sharding import flow_shard_mesh
    trace, art, backend = shard_setup
    ds = DEVICE_COUNTS[-1]
    srv = ShardedStreamingServer(art, backend, mesh=flow_shard_mesh(ds, 1),
                                 partition_classify=False, **SERVE_KW)
    _assert_matches_single_device(trace, art, backend, srv, **SERVE_KW)


def test_legacy_1d_mesh_normalizes(shard_setup):
    """A caller-built 1D ('shard',) mesh keeps working: it normalizes to
    ('shard', 'data') with a size-1 data axis, bit-identically."""
    from jax.sharding import Mesh
    trace, art, backend = shard_setup
    d = DEVICE_COUNTS[-1]
    legacy = Mesh(np.array(jax.devices()[:d]), ("shard",))
    srv = ShardedStreamingServer(art, backend, mesh=legacy, **SERVE_KW)
    assert srv.mesh.axis_names == ("shard", "data")
    assert srv.n_shards == d and srv.n_data == 1
    _assert_matches_single_device(trace, art, backend, srv, **SERVE_KW)


@pytest.mark.parametrize("make", ["flow_shard_mesh", "explicit_2d",
                                  "legacy_1d"])
def test_flow_meshes_have_auto_axes(make):
    """The streaming tier is written for Auto axes. jax.make_mesh makes
    Explicit ones by default, under which the deferral buffer's
    dynamic_update_slice is a sharding type error; every flow-table mesh
    (built here or normalized from a caller's) comes out Auto."""
    from jax.sharding import AxisType, Mesh
    from repro.distributed.sharding import as_flow_mesh, flow_shard_mesh
    devs = np.array(jax.devices()[:1])
    mesh = {"flow_shard_mesh": lambda: flow_shard_mesh(),
            "explicit_2d": lambda: as_flow_mesh(jax.make_mesh(
                (1, 1), ("shard", "data"))),
            "legacy_1d": lambda: as_flow_mesh(Mesh(devs, ("shard",)))}[make]()
    assert mesh.axis_names == ("shard", "data")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_collision_storm_uneven_ownership_never_drops_rows(shard_setup):
    """Uneven-ownership stress: a collision_storm trace concentrates
    nearly all touched buckets on whichever shards own the few target
    buckets. The static per-shard lane tile must never drop rows — lanes
    past dispatch capacity route to deferral and the StreamStats
    accounting invariant (handled + backend_rows + deferred + degraded
    == packets) still closes, bit-identically to single-device."""
    from repro.distributed.sharding import flow_shard_mesh
    from repro.netsim.scenarios import collision_storm
    _, art, backend = shard_setup
    # n_buckets must match the serving table: the storm targets buckets
    # of the SAME hash the servers use
    storm = collision_storm(n_background=150, n_attack=800,
                            n_buckets=N_BUCKETS, n_target_buckets=2,
                            pkts_per_attack=2, seed=0)
    kw = dict(n_buckets=N_BUCKETS, window=256, threshold=0.9, capacity=4)
    ds = DEVICE_COUNTS[-1]
    srv = ShardedStreamingServer(art, backend, mesh=flow_shard_mesh(ds, 1),
                                 **kw)
    s = _assert_matches_single_device(storm, art, backend, srv, **kw)
    # capacity=4 under a storm of colliding low-confidence lanes: the
    # dispatch overflow is real, and every overflowed lane is accounted
    assert s.n_deferred > 0
