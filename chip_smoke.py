#!/usr/bin/env python3
"""Smoke run of the served classifier on a TPU: ``python chip_smoke.py``.

Drives the main path once, through the entry points a user calls, with
the Pallas kernels compiled, and checks what comes out against
references that do not touch the chip. One process holds the chip for
the whole run (a child process could not reach it).

Default (one chip), two phases:

1. Flow anomaly, streaming. ``StreamingHybridServer`` with the chunked
   megastep (W=1024, K=16, capacity 64, tau 0.9) over a 2^18-bucket
   register file (8 MiB in HBM), fed by ``serve_stream(replay_source)``
   with a ``synth_trace`` of 100,000 flows (~1.1 M packets). Models are
   the streaming benches' ``trace_models``: a 4-tree depth-3 forest
   mapped to the switch tables, a 16-tree depth-6 forest as the traced
   backend. Checks: the served feature table equals the batch
   ``flow_features`` table bit for bit; predictions equal the same
   server built with ``use_pallas=False`` and run on the host CPU;
   ``StreamStats.check()`` holds; the compiled chunk step contains
   ``tpu_custom_call`` (the kernels ran, not the XLA references).
2. Finance, per request. ``HybridServer.classify`` at batch 2048 with
   the 10-tree depth-5 switch forest ``launch/serve.py`` maps and a
   32-tree depth-8 forest over the same rows as the backend, checked
   against the same CPU reference.

``--four-chips`` runs only the sharded flow table instead:
``ShardedStreamingServer`` on ('shard', 'data') meshes (4, 1) and (2, 2)
with 2^20 buckets, the same trace and models and the chunked megastep,
compared with the single-device server and the batch table.

Everything is generated from ``--seed``. Times printed are smoke
timings of one run (compilation separate), not benchmark results. The
last line of stdout is one JSON object, ``{"ok": true, "device": ...}``,
printed only when every check passed. Exits nonzero, without that line,
when JAX finds no TPU or any check fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# the CPU reference runs in this process beside the chip, so keep the
# host platform available when the environment names platforms
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

N_FLOWS = 100_000
N_BUCKETS = 1 << 18
N_BUCKETS_SHARDED = 1 << 20
WINDOW = 1024
CHUNK_WINDOWS = 16
CAPACITY = 64
THRESHOLD = 0.9
FIN_BATCH = 2048
FIN_ROWS = 60_000


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def same(a, b) -> bool:
    """Bitwise equality of two arrays (NaNs in the same places count).
    On a mismatch, prints where the arrays differ."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        print(f"  mismatch: {a.shape} {a.dtype} vs {b.shape} {b.dtype}")
        return False
    eq = (a == b) | (np.isnan(a) & np.isnan(b)) if a.dtype.kind == "f" \
        else a == b
    if eq.all():
        return True
    bad = ~eq
    cols = bad.reshape(len(a), -1).sum(axis=0) if a.ndim > 1 else bad.sum()
    i = np.argwhere(bad)[0]
    print(f"  mismatch: {int(bad.sum())} of {a.size} elements differ "
          f"(per column: {cols}); first at {tuple(i)}: "
          f"{a[tuple(i)]!r} vs {b[tuple(i)]!r}")
    return False


def counters(stats) -> dict:
    """StreamStats counters that must match exactly across devices. The
    f32 confidence sum is left out: its summation order is the
    compiler's, so it may differ in the last bits between backends."""
    d = stats.as_dict()
    return {k: v for k, v in d.items() if k not in ("conf_sum", "mean_conf")}


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def on_host():
    """Context placing new arrays on the host CPU: model training is
    set-up, and the references must not touch the chip."""
    return jax.default_device(jax.devices("cpu")[0])


def flow_models(trace, n_buckets, seed):
    """The streaming benches' forests, trained on the host."""
    from benchmarks.common import trace_models
    with on_host():
        return trace_models(trace, n_buckets, small=(4, 3, seed),
                            big=(16, 6, seed + 1))


def stream_server(art, backend, n_buckets, cls=None, **kw):
    """The phase-1 server geometry; ``cls`` picks the tier (default the
    single-device ``StreamingHybridServer``)."""
    from repro.serving.stream_serving import StreamingHybridServer
    return (cls or StreamingHybridServer)(
        art, backend, n_buckets=n_buckets, window=WINDOW,
        chunk_windows=CHUNK_WINDOWS, capacity=CAPACITY, threshold=THRESHOLD,
        **kw)


def serve(srv, trace, **kw):
    """One pass of ``serve_stream`` from a fresh state. -> (pred, stats, s)."""
    from repro.netsim.ingest import replay_source
    srv.reset()
    t0 = time.perf_counter()
    pred, stats = srv.serve_stream(replay_source(trace), **kw)
    pred = np.asarray(pred)
    return pred, stats, time.perf_counter() - t0


def cpu_reference_stream(art, backend, trace, n_buckets):
    """The same server with the XLA references, on the host CPU."""
    with on_host():
        ref = stream_server(art, backend, n_buckets, use_pallas=False)
        pred, stats, _ = serve(ref, trace, prefetch=False)
    return pred, stats


def chunk_step_hlo(srv, trace) -> str:
    """Compiled text of the server's chunk megastep for this trace's
    first chunk (the program ``serve_stream`` dispatches)."""
    from repro.netsim.stream import iter_chunks
    chunk = next(iter(iter_chunks(trace, WINDOW, CHUNK_WINDOWS,
                                  srv.n_buckets)))
    return srv._chunk_step.lower(srv.artifact, srv._state, srv._stats,
                                 chunk, jnp.float32(THRESHOLD)
                                 ).compile().as_text()


def stream_phase(seed, n_flows=N_FLOWS, n_buckets=N_BUCKETS) -> dict:
    """Phase 1: the flow-anomaly streaming path on the default device."""
    from repro.kernels.ops import classify_impl
    from repro.netsim.features import flow_features
    from repro.netsim.packets import synth_trace

    t0 = time.perf_counter()
    trace = synth_trace(n_flows=n_flows, seed=seed)
    art, backend = flow_models(trace, n_buckets, seed)
    _, batch_table = flow_features(trace, n_buckets=n_buckets)
    setup_s = time.perf_counter() - t0
    print(f"[stream] packets={trace.n_packets} flows={n_flows} "
          f"n_buckets={n_buckets} window={WINDOW} "
          f"chunk_windows={CHUNK_WINDOWS} capacity={CAPACITY} "
          f"threshold={THRESHOLD} setup_s={setup_s}", flush=True)

    srv = stream_server(jax.device_put(art, jax.devices()[0]), backend,
                        n_buckets)
    impl = classify_impl(srv.artifact, use_pallas=srv.use_pallas,
                         tiles=srv.tiles)
    print(f"[stream] use_pallas={srv.use_pallas} classify_impl={impl}",
          flush=True)
    t0 = time.perf_counter()
    hlo = chunk_step_hlo(srv, trace)
    compile_s = time.perf_counter() - t0
    # first pass compiles the jitted path (the persistent cache may
    # already hold it); the second pass is the smoke serve time
    _, _, first_s = serve(srv, trace)
    pred, stats, serve_s = serve(srv, trace)
    print(f"[stream] smoke timings (one run, not a benchmark): "
          f"aot_compile_s={compile_s} first_pass_s={first_s} "
          f"serve_s={serve_s}", flush=True)
    print(f"[stream] {stats!r}", flush=True)

    check(same(srv.flow_table(), batch_table),
          "served flow table == batch flow_features table, bit for bit")
    ref_pred, ref_stats = cpu_reference_stream(art, backend, trace,
                                               n_buckets)
    check(same(pred, ref_pred),
          f"{pred.size} predictions == CPU reference (use_pallas=False)")
    check(counters(ref_stats) == counters(stats),
          "StreamStats counters == CPU reference")
    stats.check()
    check(pred.size == trace.n_packets and stats.n_packets == pred.size,
          "StreamStats.check() holds and every packet was answered")
    return dict(impl=impl, use_pallas=srv.use_pallas,
                custom_calls=hlo.count("tpu_custom_call"),
                fraction_handled=stats.fraction_handled,
                packets=trace.n_packets)


def finance_phase(seed, n_rows=FIN_ROWS, batch=FIN_BATCH) -> dict:
    """Phase 2: the per-request tier on the finance use case."""
    from repro.core.mapping import map_tree_ensemble
    from repro.data.janestreet_like import (SWITCH_FEATURES,
                                            make_janestreet_like,
                                            train_test_split)
    from repro.kernels.ops import classify_impl
    from repro.ml.trees import fit_random_forest, predict_tree_ensemble
    from repro.serving.hybrid_serving import HybridServer

    xtr, ytr, xte, _ = train_test_split(*make_janestreet_like(n_rows,
                                                              seed=seed))
    xtr = np.asarray(xtr[:, SWITCH_FEATURES], np.float32)
    xte = np.asarray(xte[:, SWITCH_FEATURES], np.float32)
    with on_host():
        small = fit_random_forest(xtr, ytr, n_classes=2, n_trees=10,
                                  max_depth=5, seed=seed)
        big = fit_random_forest(xtr, ytr, n_classes=2, n_trees=32,
                                max_depth=8, seed=seed + 1)
        art = map_tree_ensemble(small, xtr.shape[1])
    kw = dict(threshold=0.7, capacity=1024)

    def run(srv):
        preds = [srv.classify(xte[lo:lo + batch])
                 for lo in range(0, len(xte) - batch + 1, batch)]
        return (np.concatenate([np.asarray(p) for p, _ in preds]),
                preds[-1][1].fraction_handled)

    srv = HybridServer(jax.device_put(art, jax.devices()[0]),
                       lambda r: predict_tree_ensemble(big, r), **kw)
    impl = classify_impl(srv.artifact, use_pallas=srv.use_pallas,
                         tiles=srv.tiles)
    t0 = time.perf_counter()
    run(srv)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pred, frac = run(srv)
    serve_s = time.perf_counter() - t0
    print(f"[finance] rows={pred.size} batch={batch} features="
          f"{xtr.shape[1]} switch=RF(10x5) backend=RF(32x8) "
          f"classify_impl={impl}", flush=True)
    print(f"[finance] smoke timings (one run, not a benchmark): "
          f"first_pass_s={first_s} serve_s={serve_s} "
          f"fraction_handled(last batch)={frac}", flush=True)

    with on_host():
        ref = HybridServer(art, lambda r: predict_tree_ensemble(big, r),
                           use_pallas=False, **kw)
        ref_pred, _ = run(ref)
    check(same(pred, ref_pred),
          f"{pred.size} finance predictions == CPU reference")
    return dict(impl=impl, use_pallas=srv.use_pallas)


def sharded_phase(seed, n_flows=N_FLOWS, n_buckets=N_BUCKETS_SHARDED,
                  meshes=((4, 1), (2, 2))) -> None:
    """--four-chips: the sharded flow table against one device and the
    batch table."""
    from repro.distributed.sharding import flow_shard_mesh
    from repro.netsim.features import flow_features
    from repro.netsim.packets import synth_trace
    from repro.serving.shard_serving import ShardedStreamingServer

    trace = synth_trace(n_flows=n_flows, seed=seed)
    art, backend = flow_models(trace, n_buckets, seed)
    _, batch_table = flow_features(trace, n_buckets=n_buckets)
    print(f"[sharded] packets={trace.n_packets} n_buckets={n_buckets} "
          f"window={WINDOW} chunk_windows={CHUNK_WINDOWS}", flush=True)
    one = stream_server(jax.device_put(art, jax.devices()[0]), backend,
                        n_buckets)
    one_pred, one_stats, one_s = serve(one, trace)
    print(f"[sharded] single device: use_pallas={one.use_pallas} "
          f"serve_s={one_s} (smoke, includes compile)", flush=True)
    check(same(one.flow_table(), batch_table),
          "single-device flow table == batch flow_features table")
    for shape in meshes:
        srv = stream_server(art, backend, n_buckets,
                            mesh=flow_shard_mesh(*shape),
                            cls=ShardedStreamingServer)
        _, _, first_s = serve(srv, trace)
        pred, stats, serve_s = serve(srv, trace)
        print(f"[sharded] mesh={shape} smoke timings (one run, not a "
              f"benchmark): first_pass_s={first_s} "
              f"serve_s={serve_s}", flush=True)
        for s in srv.state.regs.pkt_count.addressable_shards:
            print(f"[sharded] mesh={shape} register shard {s.index} on "
                  f"{s.device}", flush=True)
        devs = {s.device for s in srv.state.regs.pkt_count.addressable_shards}
        check(len(devs) == shape[0] * shape[1],
              f"mesh={shape}: register file spread over {len(devs)} devices")
        check(same(pred, one_pred),
              f"mesh={shape}: predictions == single-device server")
        check(counters(stats) == counters(one_stats),
              f"mesh={shape}: StreamStats counters == single-device server")
        check(same(srv.flow_table(), batch_table),
              f"mesh={shape}: flow table == batch flow_features table")
        stats.check()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded flow table on four chips")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    print(f"compile_cache={configure_compile_cache() or 'off'}", flush=True)

    if args.four_chips:
        sharded_phase(args.seed)
    else:
        res = stream_phase(args.seed)
        check(res["use_pallas"] and res["impl"] == "fused",
              f"switch classify ran the fused kernel ({res['impl']})")
        check(res["custom_calls"] > 0,
              f"compiled chunk step holds {res['custom_calls']} "
              f"tpu_custom_call ops")
        print(f"[stream] fraction_handled={res['fraction_handled']}",
              flush=True)
        fin = finance_phase(args.seed)
        check(fin["use_pallas"] and fin["impl"] == "fused",
              f"finance classify ran the fused kernel ({fin['impl']})")
    print(f"peak_bytes_in_use={peak_bytes(dev)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
