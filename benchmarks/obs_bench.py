"""Observability overhead + drift-monitor gates for the serving loop.

``python -m benchmarks.obs_bench`` measures what the observability layer
(DESIGN.md §14) costs and proves what it must never change:

* **bit-identity oracle** — a server built with an ``Observability``
  (events to a JSON-lines sink, metric rollups every ``rollup_every``
  chunks, drift monitors on, stage spans on the profiler's clock) must return
  predictions bit-identical to an obs-free server on the same replay,
  on BOTH the chunked and the per-window serving paths. Telemetry that
  changes the answer is a bug, not a feature.
* **overhead gate** — obs-on zero-sync throughput must stay >=
  ``obs_floor`` (default 0.9x) of obs-off. The hooks are host-side and
  the device stats are read once per ``rollup_every`` dispatches, so
  the budget is generous; regressing it means an accidental sync crept
  into the hot loop.
* **event-log schema** — the emitted JSON-lines file must pass
  ``validate_event_log`` (schema v1, known kinds, strictly increasing
  seq) — the log is an interchange format, not debug prints.
* **drift gates** — on a stationary trace the monitors stay silent; on
  a synthetic class-mix-shift trace (benign opening segment, then an
  anomaly-heavy segment appended after it) the ``class_mix_shift``
  detector must fire. A drift monitor that cries wolf — or sleeps
  through an attack onset — fails the bench.

Results go to ``BENCH_obs.json`` (schema "bench-v1", DESIGN.md §11);
``validate_schema.py`` additionally pins the row keys below.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import numpy as np

from benchmarks.common import print_table, trace_models, write_bench_json
from repro.netsim.ingest import replay_source
from repro.netsim.packets import synth_trace
from repro.netsim.scenarios import merge_traces
from repro.obs import DriftConfig, Observability, validate_event_log
from repro.serving.stream_serving import StreamingHybridServer


def shift_trace(n_flows=1200, seed=0, benign_frac=0.02, shifted_frac=0.9):
    """Benign opening segment, then an anomaly-heavy segment strictly
    after it: the class mix flips mid-stream (attack onset)."""
    a = synth_trace(n_flows=n_flows, anomaly_frac=benign_frac, seed=seed)
    b = synth_trace(n_flows=n_flows, anomaly_frac=shifted_frac,
                    seed=seed + 1)
    b = dataclasses.replace(b, ts=b.ts + float(a.ts.max()) + 1.0)
    return merge_traces(a, b)


def _serve_wall(srv, trace, batch, *, repeats):
    """min-over-reps zero-sync serve_stream wall time (warm server)."""
    best, preds = float("inf"), None
    for _ in range(repeats):
        srv.reset()
        t0 = time.perf_counter()
        preds, _ = srv.serve_stream(replay_source(trace, batch=batch))
        best = min(best, time.perf_counter() - t0)
    return best, np.asarray(preds)


def _overhead_rows(art, backend, trace, kw, *, chunk_windows, flush_every,
                   rollup_every, repeats, obs_floor, events_path):
    """One serving path's obs-off vs obs-on pair, oracle-gated."""
    path = "chunked" if chunk_windows else "per_window"
    skw = dict(kw, chunk_windows=chunk_windows, flush_every=flush_every)
    batch = max(1, (chunk_windows or 1) * kw["window"])

    ref = StreamingHybridServer(art, backend, **skw)
    obs = Observability(events_path=events_path,
                        rollup_every=rollup_every)
    srv = StreamingHybridServer(art, backend, obs=obs, **skw)
    # warm both (compile), then interleave reps so host noise hits the
    # off and on timings alike
    _serve_wall(ref, trace, batch, repeats=1)
    _serve_wall(srv, trace, batch, repeats=1)
    t_off = t_on = float("inf")
    for _ in range(max(repeats, 2)):
        w_off, p_off = _serve_wall(ref, trace, batch, repeats=1)
        w_on, p_on = _serve_wall(srv, trace, batch, repeats=1)
        t_off, t_on = min(t_off, w_off), min(t_on, w_on)
    obs.close()

    np.testing.assert_array_equal(p_on, p_off)     # the oracle
    n_events = validate_event_log(events_path)
    assert n_events > 0, "obs-on run emitted no events"
    assert obs.rollups.n_rows > 0, "obs-on run closed no rollup windows"

    ratio = t_off / t_on
    assert ratio >= obs_floor, (
        f"{path}: obs-on throughput {ratio:.3f}x of obs-off "
        f"(floor {obs_floor}x)")
    mk = lambda label, wall, on: {
        "config": f"{path}_{label}", "path": path, "obs_on": on,
        "n_packets": trace.n_packets,
        "pkts_per_s": round(trace.n_packets / wall, 1),
        "wall_s": round(wall, 4),
        "events": n_events if on else 0,
        "rollups": obs.rollups.n_rows if on else 0,
        "throughput_ratio": round(ratio, 3) if on else 1.0,
        "bit_identical": True,
    }
    return [mk("obs_off", t_off, False), mk("obs_on", t_on, True)], ratio


def _drift_row(art, backend, trace, kw, *, scenario, chunk_windows,
               expect_fired):
    """Serve one trace with the drift monitors on; gate what fired.

    rollup_every=1 (one window per chunk) so the baseline freezes well
    inside the benign opening segment and the shifted segment spans
    several detection windows. mix_l1=0.1: the *predicted* mix moves
    less than the true label mix (the switch model recognizes only part
    of the new traffic), so the bench threshold sits ~2x below the
    shifted windows' observed distance and ~3x above stationary
    window-to-window noise."""
    obs = Observability(rollup_every=1,
                        drift=DriftConfig(baseline_windows=2, mix_l1=0.1))
    srv = StreamingHybridServer(art, backend, chunk_windows=chunk_windows,
                                obs=obs, **kw)
    srv.serve_trace(trace)
    fired = obs.drift.fired_detectors
    alarms = [a.as_fields() for a in obs.alarms]
    if expect_fired:
        assert "class_mix_shift" in fired, (
            f"{scenario}: class_mix_shift did not fire "
            f"(fired={fired}, rollups={obs.rollups.n_rows})")
    else:
        assert not fired, f"{scenario}: spurious drift alarms: {alarms}"
    return {
        "scenario": scenario, "n_packets": trace.n_packets,
        "rollups": obs.rollups.n_rows, "fired": bool(fired),
        "detectors": list(fired), "n_alarms": len(alarms),
        "expected_fired": expect_fired,
    }


def run(n_flows=3000, window=256, chunk_windows=8, n_buckets=1 << 13,
        threshold=0.9, capacity=64, flush_every=4, rollup_every=4,
        repeats=3, seed=0, obs_floor=0.9, out="BENCH_obs.json",
        events_path="BENCH_obs_events.jsonl"):
    t_suite = time.time()
    trace = synth_trace(n_flows=n_flows, seed=seed)
    art, backend = trace_models(trace, n_buckets)
    kw = dict(n_buckets=n_buckets, window=window, threshold=threshold,
              capacity=capacity)

    # -- overhead + bit-identity, both serving paths --------------------
    rows, ratios = [], {}
    for label, ck, fe in (("chunked", chunk_windows, 1),
                          ("per_window", None, flush_every)):
        path_rows, ratio = _overhead_rows(
            art, backend, trace, kw, chunk_windows=ck, flush_every=fe,
            rollup_every=rollup_every, repeats=repeats,
            obs_floor=obs_floor, events_path=events_path)
        rows += path_rows
        ratios[label] = ratio
    print_table(f"Observability overhead (rollup_every={rollup_every})",
                ["config", "pkts/s", "ratio", "events", "rollups"],
                [[r["config"], r["pkts_per_s"], r["throughput_ratio"],
                  r["events"], r["rollups"]] for r in rows])
    for label, ratio in ratios.items():
        print(f"{label}: obs-on {ratio:.3f}x of obs-off "
              f"(floor {obs_floor}x), bit-identical")

    # -- drift monitors: silent when stationary, loud on a mix shift ----
    half = max(400, n_flows // 3)
    drift_rows = [
        _drift_row(art, backend,
                   synth_trace(n_flows=2 * half, anomaly_frac=0.02,
                               seed=seed + 7),
                   kw, scenario="stationary",
                   chunk_windows=chunk_windows, expect_fired=False),
        _drift_row(art, backend, shift_trace(n_flows=half, seed=seed + 7),
                   kw, scenario="class_mix_shift",
                   chunk_windows=chunk_windows, expect_fired=True),
    ]
    print_table("Drift monitors",
                ["scenario", "rollups", "fired", "detectors"],
                [[r["scenario"], r["rollups"], r["fired"],
                  ",".join(r["detectors"]) or "-"] for r in drift_rows])

    wall = round(time.time() - t_suite, 3)
    benches = [
        {"name": "obs_overhead", "paper_ref": "§5 switch-tier economics "
         "(telemetry must not erode them)", "ok": True, "rows": rows,
         "wall_s": wall},
        {"name": "drift_monitors", "paper_ref": "pForest phase-aware "
         "retraining triggers (ROADMAP item 1)", "ok": True,
         "rows": drift_rows, "wall_s": wall},
    ]
    if out:
        write_bench_json(out, "obs", benches,
                         config={"n_flows": n_flows, "window": window,
                                 "chunk_windows": chunk_windows,
                                 "n_buckets": n_buckets,
                                 "threshold": threshold,
                                 "capacity": capacity,
                                 "flush_every": flush_every,
                                 "rollup_every": rollup_every,
                                 "repeats": repeats,
                                 "obs_floor": obs_floor})
    if os.path.exists(events_path):
        print(f"[event log: {events_path}]")
    return rows + drift_rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args(argv)
    if args.quick:
        # short trace, fewer repeats; same oracles and gates
        run(n_flows=1000, chunk_windows=4, flush_every=2, repeats=2,
            out=args.out)
    else:
        run(out=args.out)


if __name__ == "__main__":
    main()
