"""Unified observability layer: events, metrics, timing, drift, wiring.

The contracts under test (DESIGN.md §14):

* ``EventBus`` — monotone seq, bounded ring, strict kind vocabulary,
  JSON-lines sink that ``validate_event_log`` accepts;
* ``MetricsRegistry`` — get-or-create metrics, pluggable sources (the
  shared ``as_dict()`` contract of StreamStats / FaultStats /
  IngestStats and LatencyRecorder's ``summary()``) behind one
  ``snapshot()`` that never raises;
* ``RollupWindows`` — per-N-samples keyed windows with element-wise
  list folding (class-count vectors) and partial-window flush;
* ``StageTimer`` / ``span`` — per-stage accumulation, each stage of an
  ``Observability`` inside a span of its name;
* ``DriftMonitor`` — frozen per-key baselines, the three detectors
  (conf_collapse, frac_handled_drop, class_mix_shift), min_packets
  guard, reset;
* ``LatencyRecorder`` bounded-reservoir mode — O(k) memory with exact
  n/mean/max and exact percentiles until the reservoir overflows (the
  unbounded-memory regression of open-ended serving);
* ``GuardedBackend`` lifecycle events — the EXACT event sequence of a
  breaker episode (attempt -> timeout -> retry -> OPEN -> rejected ->
  HALF_OPEN probe -> CLOSED), also under seeded FaultyBackend outage
  injection, and ``reset()`` clearing the monitor state;
* serving-tier wiring — a server built with ``obs=None`` is
  bit-identical to one with an ``Observability`` attached (chunked,
  per-window deferred, and sharded paths), rollups carry the boundary
  deltas, and the registry snapshot unifies all four stats objects.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core.mapping import map_tree_ensemble
from repro.ml.trees import fit_random_forest, predict_tree_ensemble
from repro.netsim.features import flow_features
from repro.netsim.ingest import IngestStats, LatencyRecorder, replay_source
from repro.netsim.packets import synth_trace
from repro.obs import (DriftConfig, DriftMonitor, EventBus, EventSchemaError,
                       STAGES, MetricsRegistry, Observability,
                       RollupWindows, StageTimer, validate_event_log)
from repro.serving.faults import (CLOSED, BackendFault, FaultPolicy,
                                  FaultStats, FaultyBackend, GuardedBackend)
from repro.serving.shard_serving import ShardedStreamingServer
from repro.serving.stream_serving import StreamingHybridServer

N_BUCKETS = 1 << 12


@pytest.fixture(scope="module")
def obs_setup():
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
# EventBus
# ---------------------------------------------------------------------------

def test_event_bus_seq_and_ring():
    bus = EventBus(max_events=4)
    for i in range(6):
        bus.emit("chunk", windows=i)
    assert bus.emitted == 6 and len(bus) == 4        # ring evicted 2
    seqs = [e.seq for e in bus.events]
    assert seqs == sorted(seqs) and seqs[-1] - seqs[0] == 3
    assert bus.counts() == {"chunk": 4}      # only buffered events count


def test_event_bus_rejects_unknown_kind_and_reserved_fields():
    bus = EventBus()
    with pytest.raises(EventSchemaError):
        bus.emit("not_a_kind")
    with pytest.raises(EventSchemaError):
        bus.emit("chunk", seq=7)        # shadows an envelope key
    assert bus.emitted == 0             # failed emits record nothing


def test_event_log_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs = Observability(events_path=path)
    obs.emit("serve_begin", mode="chunked")
    obs.emit("chunk", windows=8)
    obs.emit("serve_end", packets=100)
    obs.close()
    assert validate_event_log(path) == 3
    lines = [json.loads(l) for l in open(path)]
    assert [l["kind"] for l in lines] == ["serve_begin", "chunk",
                                          "serve_end"]
    assert all(l["v"] == 1 for l in lines)
    # corrupt the seq ordering -> validation must fail
    lines[2]["seq"] = lines[0]["seq"]
    with open(path, "w") as f:
        for l in lines:
            f.write(json.dumps(l) + "\n")
    with pytest.raises(EventSchemaError):
        validate_event_log(path)


# ---------------------------------------------------------------------------
# MetricsRegistry + RollupWindows
# ---------------------------------------------------------------------------

def test_registry_metrics_and_type_conflict():
    reg = MetricsRegistry()
    reg.counter("flushes").inc()
    reg.counter("flushes").inc(2)
    reg.gauge("occupancy").set(0.5)
    for v in (1.0, 2.0, 3.0):
        reg.histogram("lat").observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["flushes"] == 3
    assert snap["gauges"]["occupancy"] == 0.5
    assert snap["histograms"]["lat"]["n"] == 3
    assert snap["histograms"]["lat"]["mean"] == 2.0
    with pytest.raises(ValueError):
        reg.gauge("flushes")            # registered as a counter


def test_registry_sources_unify_stats_objects():
    """The satellite contract: StreamStats / FaultStats / IngestStats all
    expose as_dict() and route through one snapshot()."""
    reg = MetricsRegistry()
    fs, ing = FaultStats(flushes_ok=2), IngestStats(admitted=10,
                                                    count_cuts=1)
    reg.register_source("faults", fs.as_dict)
    reg.register_source("ingest", ing.as_dict)
    reg.register_source("broken", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["sources"]["faults"]["flushes_ok"] == 2
    assert snap["sources"]["ingest"]["admitted"] == 10
    assert snap["sources"]["ingest"]["cuts"] == 1      # derived key
    assert "error" in snap["sources"]["broken"]        # never raises


def test_rollup_windows_close_flush_and_vector_fold():
    rw = RollupWindows(every=2)
    assert rw.observe({"packets": 10, "class_counts": [8, 2]}) is None
    row = rw.observe({"packets": 5, "class_counts": [5, 0]})
    assert row["samples"] == 2 and row["sums"]["packets"] == 15
    assert row["sums"]["class_counts"] == [13.0, 2.0]
    rw.observe({"packets": 7}, key="tenant_b")         # keyed windows
    assert rw.flush("tenant_b")["sums"]["packets"] == 7
    assert rw.flush("tenant_b") is None                # nothing open
    assert [r["key"] for r in rw.rows] == ["default", "tenant_b"]


# ---------------------------------------------------------------------------
# StageTimer
# ---------------------------------------------------------------------------

def test_stage_timer_accumulates():
    t = iter(np.arange(0.0, 10.0, 0.5))
    timer = StageTimer(clock=lambda: next(t))
    with timer.stage("megastep"):
        pass
    with timer.stage("megastep"):
        pass
    timer.record("h2d", 0.25)
    summ = timer.summary()
    assert summ["megastep"]["n"] == 2
    assert summ["megastep"]["total_s"] == pytest.approx(1.0)
    assert summ["h2d"]["max_ms"] == pytest.approx(250.0)


# ---------------------------------------------------------------------------
# DriftMonitor
# ---------------------------------------------------------------------------

def _row(window, *, packets=1000, conf=0.95, frac=0.9, mix=(0.9, 0.1),
         key="default"):
    return {"key": key, "window": window, "samples": 1,
            "sums": {"packets": packets, "conf_sum": conf * packets,
                     "handled": int(frac * packets),
                     "class_counts": [m * packets for m in mix]}}


def test_drift_baseline_freezes_then_detects():
    mon = DriftMonitor(DriftConfig(baseline_windows=2))
    assert mon.observe(_row(0)) == []
    assert mon.observe(_row(1)) == []                  # baseline windows
    assert mon.baseline_ready()
    assert mon.observe(_row(2)) == []                  # stationary: silent
    fired = mon.observe(_row(3, conf=0.6, frac=0.5, mix=(0.2, 0.8)))
    assert {a.detector for a in fired} == {"conf_collapse",
                                           "frac_handled_drop",
                                           "class_mix_shift"}
    a = next(a for a in fired if a.detector == "conf_collapse")
    assert a.baseline == pytest.approx(0.95) and a.value == pytest.approx(0.6)
    mon.reset()
    assert not mon.fired and not mon.baseline_ready()


def test_drift_min_packets_guard_and_disabled_detectors():
    mon = DriftMonitor(DriftConfig(baseline_windows=1, min_packets=64,
                                   conf_drop=None, frac_drop=None))
    assert mon.observe(_row(0, packets=10)) == []      # ignored entirely
    assert not mon.baseline_ready()
    mon.observe(_row(1))
    fired = mon.observe(_row(2, conf=0.1, frac=0.1, mix=(0.1, 0.9)))
    assert [a.detector for a in fired] == ["class_mix_shift"]


def test_drift_per_key_baselines():
    mon = DriftMonitor(DriftConfig(baseline_windows=1))
    mon.observe(_row(0, key="a"))
    mon.observe(_row(0, key="b", mix=(0.1, 0.9)))
    assert mon.observe(_row(1, key="a", mix=(0.9, 0.1))) == []
    fired = mon.observe(_row(1, key="b", mix=(0.9, 0.1)))
    assert [a.detector for a in fired] == ["class_mix_shift"]
    assert fired[0].key == "b"


# ---------------------------------------------------------------------------
# LatencyRecorder bounded reservoir (the unbounded-memory regression)
# ---------------------------------------------------------------------------

def test_latency_recorder_unbounded_unchanged():
    rec = LatencyRecorder()
    rec.record(np.array([1.0, 2.0]), 3.0)
    rec.record(np.array([2.5]), 3.0)
    np.testing.assert_allclose(rec.latencies(), [2.0, 1.0, 0.5])
    s = rec.summary()
    assert s["n"] == 3
    assert s["mean_ms"] == pytest.approx(3500.0 / 3)
    assert s["max_ms"] == pytest.approx(2000.0)


def test_latency_recorder_reservoir_bounds_memory_exact_until_full():
    rec = LatencyRecorder(max_samples=8)
    rec.record(np.arange(5, dtype=np.float64), 5.0)    # spans 5..1
    assert rec.n == 5 and rec.latencies().size == 5
    exact = LatencyRecorder()
    exact.record(np.arange(5, dtype=np.float64), 5.0)
    assert rec.summary() == exact.summary()            # exact until full
    # overflow: memory stays at k, n/mean/max stay exact over all seen
    rng = np.random.default_rng(0)
    admits = rng.uniform(0.0, 1.0, 10_000)
    rec.record(admits, 2.0)
    assert rec.latencies().size == 8                   # O(k), not O(n)
    s = rec.summary()
    assert s["n"] == 10_005
    true_spans = np.concatenate([5.0 - np.arange(5), 2.0 - admits])
    assert s["mean_ms"] == pytest.approx(true_spans.mean() * 1e3)
    assert s["max_ms"] == pytest.approx(5000.0)
    # the reservoir percentile is a sample estimate of the true one
    assert abs(s["p50_ms"] - np.percentile(true_spans * 1e3, 50)) < 700.0


def test_latency_recorder_seeded_determinism_and_validation():
    a, b = LatencyRecorder(max_samples=4, seed=7), \
        LatencyRecorder(max_samples=4, seed=7)
    for rec in (a, b):
        rec.record(np.linspace(0, 1, 100), 2.0)
    np.testing.assert_array_equal(a.latencies(), b.latencies())
    with pytest.raises(ValueError):
        LatencyRecorder(max_samples=0)


def test_serve_stream_latency_samples_bounds_recorder(obs_setup):
    trace, art, backend = obs_setup
    srv = StreamingHybridServer(art, backend, n_buckets=N_BUCKETS,
                                window=128, chunk_windows=4)
    srv.serve_stream(replay_source(trace), record_latency=True,
                     latency_samples=32)
    assert srv.latency.max_samples == 32
    assert srv.latency.n == trace.n_packets            # n stays exact
    assert srv.latency.latencies().size == 32


# ---------------------------------------------------------------------------
# GuardedBackend lifecycle events (the exact breaker sequence)
# ---------------------------------------------------------------------------

def test_breaker_event_sequence_exact():
    """One full breaker episode, event by event: first flush times out
    then errors (failed), second flush fails twice more -> OPEN, third is
    rejected while cooling down, fourth is the HALF_OPEN probe -> CLOSED."""
    import threading
    release = threading.Event()
    calls = {"i": 0}

    def backend(rows):
        i = calls["i"]
        calls["i"] += 1
        if i == 0:
            release.wait(5.0)           # abandoned by the 30ms timeout
        if i in (1, 2, 3):
            raise BackendFault(f"scripted failure {i}")
        return np.zeros(4, np.int32)

    bus = EventBus()
    guard = GuardedBackend(
        backend, FaultPolicy(timeout_s=0.03, max_retries=1,
                             backoff_base_s=0.0, breaker_threshold=2,
                             breaker_cooldown=1),
        sleep=lambda s: None, events=bus)
    try:
        assert guard(np.zeros((4, 8))) is None         # flush 1: failed
    finally:
        release.set()                   # unstick the abandoned worker
    assert guard(np.zeros((4, 8))) is None             # flush 2: -> OPEN
    assert guard(np.zeros((4, 8))) is None             # flush 3: rejected
    out = guard(np.zeros((4, 8)))                      # flush 4: probe ok
    np.testing.assert_array_equal(out, np.zeros(4, np.int32))
    assert [e.kind for e in bus.events] == [
        "backend_attempt", "backend_timeout",          # flush 1
        "backend_retry", "backend_attempt", "backend_error",
        "flush_failed",
        "backend_attempt", "backend_error",            # flush 2
        "backend_retry", "backend_attempt", "backend_error",
        "flush_failed", "breaker_open",
        "flush_rejected",                              # flush 3
        "breaker_half_open", "backend_attempt",        # flush 4 (probe)
        "flush_ok", "breaker_close",
    ]
    assert guard.stats.breaker_opens == 1
    assert guard.stats.breaker_closes == 1


def test_breaker_events_under_faulty_backend_injection():
    """Same lifecycle driven by seeded FaultyBackend outages instead of a
    scripted backend: deterministic OPEN -> probe -> CLOSED."""
    be = FaultyBackend(lambda rows: np.zeros(len(rows), np.int32),
                       outages=range(0, 4), seed=0)
    bus = EventBus()
    guard = GuardedBackend(
        be, FaultPolicy(max_retries=1, backoff_base_s=0.0,
                        breaker_threshold=2, breaker_cooldown=1),
        sleep=lambda s: None, events=bus)
    assert guard(np.zeros((2, 8))) is None             # outages 0,1
    assert guard(np.zeros((2, 8))) is None             # outages 2,3 -> OPEN
    assert guard(np.zeros((2, 8))) is None             # rejected (cooldown)
    assert guard(np.zeros((2, 8))) is not None         # probe succeeds
    kinds = [e.kind for e in bus.events]
    assert kinds.count("breaker_open") == 1
    assert kinds.count("flush_rejected") == 1
    assert kinds.index("breaker_half_open") < kinds.index("breaker_close")
    assert kinds[-1] == "breaker_close"


def test_guard_reset_clears_monitor_state_and_emits():
    bus = EventBus()
    guard = GuardedBackend(
        lambda rows: (_ for _ in ()).throw(BackendFault("down")),
        FaultPolicy(max_retries=0, backoff_base_s=0.0,
                    breaker_threshold=1, breaker_cooldown=2),
        sleep=lambda s: None, events=bus)
    assert guard(np.zeros((2, 8))) is None
    assert guard.stats.breaker_opens == 1
    guard.reset()
    assert guard.state == CLOSED
    assert guard.stats == FaultStats()                 # telemetry cleared
    assert guard.consecutive_failures == 0
    assert bus.events[-1].kind == "guard_reset"
    # construction-time reset() must NOT have emitted (events bound after)
    assert [e.kind for e in bus.events].count("guard_reset") == 1


# ---------------------------------------------------------------------------
# Serving-tier wiring: bit-identity, rollups, unified snapshot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path_kw", [
    {"chunk_windows": 4},                       # chunked megastep
    {"flush_every": 1},                         # per-window immediate
    {"flush_every": 3},                         # per-window deferred
], ids=["chunked", "per_window", "deferred"])
def test_obs_bit_identity_single_device(obs_setup, path_kw):
    trace, art, backend = obs_setup
    kw = dict(n_buckets=N_BUCKETS, window=128, **path_kw)
    ref_preds, ref_stats = StreamingHybridServer(
        art, backend, **kw).serve_trace(trace)
    obs = Observability(rollup_every=2)
    srv = StreamingHybridServer(art, backend, obs=obs, **kw)
    preds, stats = srv.serve_trace(trace)
    np.testing.assert_array_equal(np.asarray(preds), np.asarray(ref_preds))
    assert stats == ref_stats
    assert obs.events.counts()["serve_begin"] == 1
    assert obs.rollups.n_rows > 0
    # rollup deltas reconcile with the final stats
    total = sum(r["sums"]["packets"] for r in obs.rollups.rows)
    assert total == stats.n_packets


def test_obs_bit_identity_sharded(obs_setup):
    trace, art, backend = obs_setup
    kw = dict(n_buckets=N_BUCKETS, window=128, n_shards=1)
    ref_preds, ref_stats = ShardedStreamingServer(
        art, backend, **kw).serve_trace(trace)
    obs = Observability(rollup_every=2)
    srv = ShardedStreamingServer(art, backend, obs=obs, **kw)
    preds, stats = srv.serve_trace(trace)
    np.testing.assert_array_equal(np.asarray(preds), np.asarray(ref_preds))
    assert stats == ref_stats
    assert obs.rollups.n_rows > 0


def test_obs_snapshot_unifies_server_telemetry(obs_setup):
    trace, art, backend = obs_setup
    obs = Observability(rollup_every=2)
    srv = StreamingHybridServer(art, backend, n_buckets=N_BUCKETS,
                                window=128, chunk_windows=4,
                                fault_policy=FaultPolicy(max_retries=0),
                                obs=obs)
    srv.serve_stream(replay_source(trace), record_latency=True)
    snap = obs.snapshot()
    src = snap["sources"]
    assert src["server.stream"]["packets"] == trace.n_packets
    assert src["server.stream"]["conf_sum"] > 0
    assert 0.0 <= src["server.stream"]["mean_conf"] <= 1.0
    assert src["server.faults"]["flushes_ok"] == srv.fault_stats.flushes_ok
    assert src["server.ingest"]["admitted"] == trace.n_packets
    assert src["server.latency"]["n"] == trace.n_packets
    assert "megastep" in snap["stages"]
    assert snap["events"]["emitted"] == obs.events.emitted
    assert snap["drift"]["enabled"] and snap["drift"]["alarms"] == []


def test_obs_stats_as_dict_contract(obs_setup):
    """StreamStats.as_dict carries the additive counters + derived
    ratios, and the accounting invariant survives the conf_sum field."""
    trace, art, backend = obs_setup
    srv = StreamingHybridServer(art, backend, n_buckets=N_BUCKETS,
                                window=128, chunk_windows=4)
    _, stats = srv.serve_trace(trace)
    d = stats.as_dict()
    assert d["handled"] + d["backend_rows"] + d["deferred"] \
        + d["degraded"] == d["packets"]
    assert d["fraction_handled"] == pytest.approx(stats.fraction_handled)
    assert d["mean_conf"] == pytest.approx(d["conf_sum"] / d["packets"])
    for cls in (IngestStats, FaultStats):
        assert isinstance(cls().as_dict(), dict)


def test_obs_sampled_sync_and_stage_timing_bit_identical(obs_setup,
                                                          monkeypatch):
    """The serving loop's stages are timed, each inside a span of its
    name, with the predictions bit-identical and no device sync added:
    outside the reads of ``StreamStats`` that the rollups make by design,
    the loop waits on and reads back device arrays exactly as often with
    obs attached as without. Waits and reads are counted on the array
    itself: ``block_until_ready`` and the batched wait, the buffer that
    ``np.asarray`` takes, and ``_value``, which ``device_get``, ``int``
    and ``float`` go through."""
    import sys
    from collections import Counter

    from jax._src import api
    from jax._src import array as jarray

    import repro.obs
    from repro.serving.stream_serving import StreamStats
    trace, art, backend = obs_setup
    kw = dict(n_buckets=N_BUCKETS, window=128, chunk_windows=4)
    opened, syncs = [], Counter()
    real_span = repro.obs.span
    monkeypatch.setattr(repro.obs, "span", lambda name, **ids: (
        opened.append(name), real_span(name, **ids))[1])

    def counted(kind, real):
        def f(*a):
            fr = sys._getframe(1)
            while fr is not None and not isinstance(fr.f_locals.get("self"),
                                                    StreamStats):
                fr = fr.f_back
            if fr is None:
                syncs[kind] += 1
            return real(*a)
        return f

    value = jarray.ArrayImpl._value
    monkeypatch.setattr(jarray.ArrayImpl, "_value",
                        property(counted("read", value.fget)))
    monkeypatch.setattr(jarray.ArrayImpl, "__buffer__", counted(
        "read", jarray.ArrayImpl.__buffer__))
    monkeypatch.setattr(jarray.ArrayImpl, "block_until_ready", counted(
        "wait", jarray.ArrayImpl.block_until_ready))
    monkeypatch.setattr(api.xc, "batched_block_until_ready", counted(
        "wait", api.xc.batched_block_until_ready))
    ref, _ = StreamingHybridServer(art, backend, **kw).serve_trace(trace)
    without_obs = syncs.copy()
    syncs.clear()
    obs = Observability(rollup_every=2)
    srv = StreamingHybridServer(art, backend, obs=obs, **kw)
    preds, _ = srv.serve_trace(trace)
    with_obs = syncs.copy()
    np.testing.assert_array_equal(np.asarray(preds), np.asarray(ref))
    n = obs.timer.count("megastep")
    assert n > 0 and opened.count("megastep") == n
    assert set(obs.timer.stages) <= set(STAGES) and set(opened) == set(
        obs.timer.stages)
    assert without_obs["read"] > 0          # the counting sees the loop
    assert with_obs == without_obs


def test_obs_drift_fires_on_class_mix_shift_trace(obs_setup):
    """End-to-end drift: a benign segment then an anomaly-heavy segment
    appended after it trips class_mix_shift; the stationary replay of
    the same benign trace stays silent (same thresholds)."""
    trace, art, backend = obs_setup
    kw = dict(n_buckets=N_BUCKETS, window=128, chunk_windows=2)
    drift = DriftConfig(baseline_windows=2, mix_l1=0.1)

    obs_flat = Observability(rollup_every=1, drift=drift)
    StreamingHybridServer(art, backend, obs=obs_flat,
                          **kw).serve_trace(trace)
    assert not obs_flat.drift.fired, obs_flat.alarms

    shifted = synth_trace(n_flows=300, anomaly_frac=0.95, seed=4)
    shifted = dataclasses.replace(
        shifted, ts=shifted.ts + float(trace.ts.max()) + 1.0)
    from repro.netsim.scenarios import merge_traces
    both = merge_traces(trace, shifted)
    obs = Observability(rollup_every=1, drift=drift)
    StreamingHybridServer(art, backend, obs=obs, **kw).serve_trace(both)
    assert "class_mix_shift" in obs.drift.fired_detectors, \
        obs.drift.fired_detectors
    assert obs.events.counts().get("drift_alarm", 0) == len(obs.alarms)


def test_obs_flush_and_autotune_events(obs_setup):
    """The per-window deferred path narrates its flush lifecycle, and
    chunk_windows='auto' records the autotune decision."""
    trace, art, backend = obs_setup
    obs = Observability(rollup_every=4)
    srv = StreamingHybridServer(art, backend, n_buckets=N_BUCKETS,
                                window=128, flush_every=3, obs=obs)
    srv.serve_trace(trace)
    counts = obs.events.counts()
    assert counts["flush"] >= 1 and counts["backpatch"] >= 1
    triggers = {e.fields["trigger"] for e in obs.events.of("flush")}
    assert "end_of_stream" in triggers or "cycle_full" in triggers

    obs2 = Observability()
    StreamingHybridServer(art, backend, n_buckets=N_BUCKETS, window=128,
                          chunk_windows="auto", autotune=False, obs=obs2)
    auto = obs2.events.of("autotune")
    assert len(auto) == 1 and auto[0].fields["knob"] == "chunk_windows"
