"""Streaming hybrid serving: the always-on switch, one window at a time.

``StreamingHybridServer`` extends the zero-sync ``HybridServer`` with the
register-file carry of ``netsim.stream``: each ``step(window)`` is ONE
jitted, buffer-donating device dispatch that fuses

  register update        (segment-scatter into the donated FlowTableState)
  feature read-out       (gather the updated table rows for the window's
                          touched flows — per-packet, as a switch
                          classifies each arriving packet with its flow's
                          registers)
  fused switch classify  (the single-matmul kernel pipeline)
  capacity-bounded dispatch -> backend -> combine
  telemetry accumulation (StreamStats carried as donated device arrays)

Nothing in ``step`` touches the host: state and running statistics are
device arrays donated back in, per-window telemetry returns as a lazy
``HybridStats``, and predictions stay on device until the caller reads
them. Donation discipline (also DESIGN.md §5): the register file and the
stats carry are consumed every step and replaced by the returned pytrees —
callers must never hold a reference to a previous state.

Backends that cannot trace fall back to the same two-phase shape as
``HybridServer``: jitted update+switch+dispatch (still donating state),
host backend call, jitted combine+stats (donating the stats carry).

Cross-window backend batching (DESIGN.md §7): ``flush_every=k`` defers
the dispatched low-confidence rows of up to k windows into a donated
``core.hybrid.DeferredDispatch`` buffer and runs the backend once per
flush at k-times the occupancy; the answers back-patch the per-window
pending prediction set at their recorded (window, lane) return
addresses. ``flush_every=1`` (default) is the unchanged per-window path
— the equivalence oracle; final predictions are bit-identical either
way for row-wise backends.

Open-ended ingest (DESIGN.md §13): ``serve_stream(source)`` is the
primary serving loop — a pull-based pipeline over ``netsim.ingest``'s
ring buffer (count/deadline window-granular cuts, optional prefetch
double-buffering of chunk transfers, per-packet admit->prediction
latency percentiles). ``serve_trace`` is its thin finite-replay wrapper,
bit-identical to the pre-refactor trace loop. ``chunk_windows="auto"``
runs a measured K sweep at init (``autotune_chunk_windows``) that can
never select a chunk size regressing versus ``DEFAULT_CHUNK_WINDOWS``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.artifact import TableArtifact
from repro.core.hybrid import (DeferredDispatch, backpatch_pending,
                               chunk_dispatch, combine, defer_window,
                               dispatch, init_deferred)
from repro.kernels.ops import fused_classify
from repro.kernels.tuning import (TileConfig, measure_min,
                                  resolve_use_pallas, sweep_best,
                                  _artifact_key)
from repro.netsim.ingest import (LatencyRecorder, PacketRingBuffer,
                                 cut_stream, prefetch_iter, replay_source)
from repro.netsim.stream import (EVICT_POLICIES, FLOW_FEATURES,
                                 FlowTableState, PacketChunk, PacketWindow,
                                 chunk_update_readout, flow_table_readout,
                                 init_flow_table, window_update_readout)
from repro.obs import Observability
from repro.serving.faults import FaultPolicy, FaultStats, GuardedBackend
from repro.serving.hybrid_serving import HybridServer, HybridStats


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StreamStats:
    """Running telemetry over all windows served — scalar device arrays.

    Constructed and updated entirely on device (the carry is donated into
    every step); reading any python-typed property below is the only point
    that syncs, mirroring HybridStats' laziness.
    """
    windows: jax.Array        # i32: windows served
    packets: jax.Array        # i32: valid packets seen
    handled: jax.Array        # i32: answered at the switch tier
    backend_rows: jax.Array   # i32: rows the backend actually served
    deferred: jax.Array       # i32: low-confidence rows past capacity that
                              #      never reached the backend (switch
                              #      answer kept — was silent before)
    degraded: jax.Array       # i32: dispatched rows whose backend flush
                              #      ultimately failed (fault policy) —
                              #      provisional switch answer kept
    flushes: jax.Array        # i32: successful backend invocations (one
                              #      per served flush; == windows when
                              #      flush_every == 1 and nothing degrades)
    evicted: jax.Array        # i32: buckets recycled by the aging sweep
    overflow: jax.Array       # i32: register slots newly saturated at 2^24
    conf_sum: jax.Array       # f32: switch confidence summed over valid
                              #      lanes — mean_conf = conf_sum/packets
                              #      is the drift monitors' confidence-
                              #      collapse signal (ROADMAP item 1)

    @classmethod
    def zero(cls) -> "StreamStats":
        z = lambda: jnp.zeros((), jnp.int32)
        return cls(windows=z(), packets=z(), handled=z(), backend_rows=z(),
                   deferred=z(), degraded=z(), flushes=z(), evicted=z(),
                   overflow=z(), conf_sum=jnp.zeros((), jnp.float32))

    @property
    def n_windows(self) -> int:
        return int(self.windows)

    @property
    def n_packets(self) -> int:
        return int(self.packets)

    @property
    def n_handled(self) -> int:
        """Packets answered confidently at the switch tier."""
        return int(self.handled)

    @property
    def fraction_handled(self) -> float:
        n = int(self.packets)
        return float(self.handled) / n if n else 0.0

    @property
    def total_backend_rows(self) -> int:
        return int(self.backend_rows)

    @property
    def n_deferred(self) -> int:
        """Low-confidence rows that overflowed the dispatch capacity and
        kept the (low-confidence) switch answer. Nonzero means the stream
        wants a larger ``capacity`` or a larger ``flush_every`` — visible
        accounting for what used to be a silent drop. After the final
        flush, ``handled + backend_rows + deferred + degraded ==
        packets`` (see ``check``)."""
        return int(self.deferred)

    @property
    def n_degraded(self) -> int:
        """Dispatched rows whose backend flush ultimately failed under a
        ``FaultPolicy`` — the tier degraded to switch-only for them: the
        provisional switch prediction was kept, the back-patch skipped.
        Always 0 without a fault policy (no failure path exists)."""
        return int(self.degraded)

    @property
    def n_flushes(self) -> int:
        """Successful backend invocations so far: one per window at
        flush_every=1, one per ``flush_every`` windows (plus the
        end-of-trace flush) under cross-window batching. A flush that
        ultimately fails under a ``FaultPolicy`` does not count — its
        rows land in ``degraded``."""
        return int(self.flushes)

    @property
    def n_evicted(self) -> int:
        """Buckets recycled by the aging sweep (0 when eviction is off)."""
        return int(self.evicted)

    @property
    def n_overflow(self) -> int:
        """Register slots that hit the 2^24 exactness envelope; nonzero
        means count features saturated and the stream needs eviction (or
        more buckets) — the guard makes that visible, not silent."""
        return int(self.overflow)

    @property
    def total_conf(self) -> float:
        """Switch confidence summed over all valid packets."""
        return float(self.conf_sum)

    @property
    def mean_conf(self) -> float:
        """Mean switch confidence per valid packet — the signal whose
        windowed drop is the confidence-collapse drift detector."""
        n = int(self.packets)
        return float(self.conf_sum) / n if n else 0.0

    def as_dict(self) -> dict:
        """Host-side snapshot (syncs every counter) — the same plain-dict
        contract as ``FaultStats.as_dict``/``IngestStats.as_dict``, so
        the obs metrics registry reports all tiers uniformly. Counter
        keys are additive (deltas between two snapshots are meaningful);
        the two trailing ratios are derived, not additive."""
        return {"windows": self.n_windows, "packets": self.n_packets,
                "handled": self.n_handled,
                "backend_rows": self.total_backend_rows,
                "deferred": self.n_deferred, "degraded": self.n_degraded,
                "flushes": self.n_flushes, "evicted": self.n_evicted,
                "overflow": self.n_overflow, "conf_sum": self.total_conf,
                "fraction_handled": self.fraction_handled,
                "mean_conf": self.mean_conf}

    def check(self) -> "StreamStats":
        """Assert the accounting invariant: every valid packet is answered
        exactly once — confidently at the switch (``handled``), by the
        backend (``backend_rows``), by a kept switch answer past dispatch
        capacity (``deferred``), or by a kept switch answer on a failed
        flush (``degraded``):

            handled + backend_rows + deferred + degraded == packets

        Holds whenever no flush is pending; ``serve_trace`` calls it after
        the guaranteed end-of-trace flush. Reading the counters syncs (the
        caller is already at a sync point there). Returns self."""
        n = (self.n_handled + self.total_backend_rows + self.n_deferred
             + self.n_degraded)
        if n != self.n_packets:
            raise AssertionError(
                f"StreamStats accounting invariant violated: "
                f"handled={self.n_handled}"
                f" + backend_rows={self.total_backend_rows}"
                f" + deferred={self.n_deferred}"
                f" + degraded={self.n_degraded} = {n}"
                f" != packets={self.n_packets}")
        return self

    def __repr__(self):
        return (f"StreamStats(windows={self.n_windows}, "
                f"packets={self.n_packets}, "
                f"fraction_handled={self.fraction_handled:.3f}, "
                f"backend_rows={self.total_backend_rows}, "
                f"deferred={self.n_deferred}, degraded={self.n_degraded}, "
                f"flushes={self.n_flushes}, "
                f"evicted={self.n_evicted}, overflow={self.n_overflow})")


def accumulate_stream_stats(stats: StreamStats, w: PacketWindow, sw_pred,
                            be_pred, idx, valid, fwd, conf, n_evicted,
                            n_overflow):
    """Shared jit-traceable epilogue: combine backend answers, mask pad
    lanes, fold this window into the running StreamStats. Used by both the
    single-device and the sharded step (the sharded one passes psummed
    inputs — already replicated, so the fold is identical per device).
    The backend ran for this window, so ``flushes`` advances by one;
    forwarded rows past capacity land in ``deferred`` instead of silently
    keeping the switch answer uncounted. ``conf`` is the switch-tier
    confidence vector — valid lanes fold into ``conf_sum``.
    Returns (stats, pred, frac_handled, backend_rows)."""
    pred = combine(sw_pred, be_pred, idx, valid)
    pred = jnp.where(w.valid, pred, -1)                  # pad lanes
    n_valid = jnp.sum(w.valid.astype(jnp.int32))
    n_handled = jnp.sum((w.valid & ~fwd).astype(jnp.int32))
    n_fwd = jnp.sum(fwd.astype(jnp.int32))
    rows = jnp.sum(valid.astype(jnp.int32))
    frac = (n_handled.astype(jnp.float32)
            / jnp.maximum(n_valid, 1).astype(jnp.float32))
    stats = dataclasses.replace(
        stats, windows=stats.windows + 1,
        packets=stats.packets + n_valid,
        handled=stats.handled + n_handled,
        backend_rows=stats.backend_rows + rows,
        deferred=stats.deferred + (n_fwd - rows),
        flushes=stats.flushes + 1,
        evicted=stats.evicted + n_evicted,
        overflow=stats.overflow + n_overflow,
        conf_sum=stats.conf_sum + _fold_conf(conf, w.valid))
    return stats, pred, frac, rows


def _fold_conf(conf, valid):
    """Valid-lane confidence sum (f32 scalar) for the conf_sum fold."""
    return jnp.sum(jnp.where(valid, conf, 0.0).astype(jnp.float32))


def degrade_window_stats(stats: StreamStats, w: PacketWindow, sw_pred, fwd,
                         valid, conf, n_evicted, n_overflow):
    """Degraded epilogue for the per-window (flush_every=1) two-phase
    path: this window's backend flush ultimately failed under the fault
    policy, so every dispatched row keeps its provisional switch-tier
    prediction — counted in ``degraded``, not ``backend_rows``, and
    ``flushes`` does not advance (it counts successful invocations).
    Returns (stats, pred, frac_handled, rows_degraded)."""
    pred = jnp.where(w.valid, sw_pred, -1)               # pad lanes
    n_valid = jnp.sum(w.valid.astype(jnp.int32))
    n_handled = jnp.sum((w.valid & ~fwd).astype(jnp.int32))
    n_fwd = jnp.sum(fwd.astype(jnp.int32))
    rows = jnp.sum(valid.astype(jnp.int32))
    frac = (n_handled.astype(jnp.float32)
            / jnp.maximum(n_valid, 1).astype(jnp.float32))
    stats = dataclasses.replace(
        stats, windows=stats.windows + 1,
        packets=stats.packets + n_valid,
        handled=stats.handled + n_handled,
        deferred=stats.deferred + (n_fwd - rows),
        degraded=stats.degraded + rows,
        evicted=stats.evicted + n_evicted,
        overflow=stats.overflow + n_overflow,
        conf_sum=stats.conf_sum + _fold_conf(conf, w.valid))
    return stats, pred, frac, rows


def accumulate_deferred_stats(stats: StreamStats, w: PacketWindow, fwd,
                              valid, conf, n_evicted, n_overflow):
    """Per-window stats fold for the deferred-dispatch path: everything
    *except* the backend accounting, which folds at flush time
    (``fold_flush_stats``) when the backend actually runs.
    Returns (stats, frac_handled, rows_deferred_this_window)."""
    n_valid = jnp.sum(w.valid.astype(jnp.int32))
    n_handled = jnp.sum((w.valid & ~fwd).astype(jnp.int32))
    n_fwd = jnp.sum(fwd.astype(jnp.int32))
    rows = jnp.sum(valid.astype(jnp.int32))
    frac = (n_handled.astype(jnp.float32)
            / jnp.maximum(n_valid, 1).astype(jnp.float32))
    stats = dataclasses.replace(
        stats, windows=stats.windows + 1,
        packets=stats.packets + n_valid,
        handled=stats.handled + n_handled,
        deferred=stats.deferred + (n_fwd - rows),
        evicted=stats.evicted + n_evicted,
        overflow=stats.overflow + n_overflow,
        conf_sum=stats.conf_sum + _fold_conf(conf, w.valid))
    return stats, frac, rows


def fold_flush_stats(stats: StreamStats, dd: DeferredDispatch) -> StreamStats:
    """One backend flush served every live slot of the deferral buffer."""
    rows = jnp.sum(dd.valid.astype(jnp.int32))
    return dataclasses.replace(stats, backend_rows=stats.backend_rows + rows,
                               flushes=stats.flushes + 1)


def fold_degraded_flush(stats: StreamStats,
                        dd: DeferredDispatch) -> StreamStats:
    """Flush-time fold when the backend ultimately failed: the cycle's
    deferred rows keep their provisional switch predictions (the
    back-patch is skipped) and land in ``degraded``; ``flushes`` does not
    advance — it counts successful backend invocations only."""
    rows = jnp.sum(dd.valid.astype(jnp.int32))
    return dataclasses.replace(stats, degraded=stats.degraded + rows)


def degrade_chunk_stats(stats: StreamStats,
                        dd: DeferredDispatch) -> StreamStats:
    """Corrective fold for a degraded chunk flush:
    ``accumulate_chunk_stats`` folds the backend accounting inside the
    jitted switch half, *before* the host backend runs — when the flush
    then ultimately fails, move its rows to ``degraded`` and retract the
    optimistic flush count."""
    rows = jnp.sum(dd.valid.astype(jnp.int32))
    return dataclasses.replace(
        stats, backend_rows=stats.backend_rows - rows,
        degraded=stats.degraded + rows, flushes=stats.flushes - 1)


def defer_tail(stats, dd, pending, w: PacketWindow, sw_pred, fwd, buf, idx,
               valid, conf, counts, pos):
    """Shared tail of the deferred-path window step (single-device and
    sharded): mask pad lanes, append the dispatched rows to the deferral
    buffer at cycle slot ``pos``, record the provisional predictions in
    the pending set, fold the non-backend stats.
    Returns (stats, dd, pending, pred, frac, rows)."""
    pred = jnp.where(w.valid, sw_pred, -1)                   # pad lanes
    dd = defer_window(dd, buf, idx, valid, pos)
    pending = pending.at[pos].set(pred)
    stats, frac, rows = accumulate_deferred_stats(stats, w, fwd, valid,
                                                  conf, *counts)
    return stats, dd, pending, pred, frac, rows


def chunk_classify_tail(art, stats, chunk, xs, n_ev, n_ov, threshold,
                        capacity: int, *, use_pallas, tiles):
    """Shared batched half of the chunk megastep (single-device and
    sharded), after the sequential register scan produced the (K, W, 8)
    readout rows: ONE fused classify over all K*W rows, vmapped
    capacity-bounded dispatch, the whole-chunk stats fold, and the
    provisional prediction set (pad/dead lanes at -1). Bit-identical to
    K per-window passes because every op is row-independent.
    Returns (stats, dd, pending, frac, rows)."""
    k, w_lanes, nf = xs.shape
    with jax.named_scope("fused_classify"):
        sw_pred, conf = fused_classify(art, xs.reshape(k * w_lanes, nf),
                                       use_pallas=use_pallas, tiles=tiles)
    sw_pred = sw_pred.reshape(k, w_lanes).astype(jnp.int32)
    conf = conf.reshape(k, w_lanes)
    fwd = (conf < threshold) & chunk.valid
    dd = chunk_dispatch(xs, fwd, capacity)
    stats, frac, rows = accumulate_chunk_stats(stats, chunk, fwd, dd,
                                               conf, n_ev, n_ov)
    pending = jnp.where(chunk.valid, sw_pred, -1)        # pad/dead lanes
    return stats, dd, pending, frac, rows


def accumulate_chunk_stats(stats: StreamStats, chunk, fwd,
                           dd: DeferredDispatch, conf, n_evicted,
                           n_overflow):
    """Whole-chunk stats fold: the per-window telemetry identities summed
    over the (K, W) chunk in one pass (dead pad windows contribute no
    valid lanes, and are masked out of the window count), plus the
    backend accounting for the chunk's single flush.
    Returns (stats, frac_handled, backend_rows)."""
    n_valid = jnp.sum(chunk.valid.astype(jnp.int32))
    n_handled = jnp.sum((chunk.valid & ~fwd).astype(jnp.int32))
    n_fwd = jnp.sum(fwd.astype(jnp.int32))
    rows = jnp.sum(dd.valid.astype(jnp.int32))
    live = jnp.sum(jnp.any(chunk.valid, axis=1).astype(jnp.int32))
    frac = (n_handled.astype(jnp.float32)
            / jnp.maximum(n_valid, 1).astype(jnp.float32))
    stats = dataclasses.replace(
        stats, windows=stats.windows + live,
        packets=stats.packets + n_valid,
        handled=stats.handled + n_handled,
        backend_rows=stats.backend_rows + rows,
        deferred=stats.deferred + (n_fwd - rows),
        flushes=stats.flushes + 1,
        evicted=stats.evicted + n_evicted,
        overflow=stats.overflow + n_overflow,
        conf_sum=stats.conf_sum + _fold_conf(conf, chunk.valid))
    return stats, frac, rows


# -- chunk-size autotuning ---------------------------------------------------

DEFAULT_CHUNK_WINDOWS = 16
CHUNK_WINDOW_CANDIDATES = (4, 8, 16, 32)

_CHUNK_TUNE_CACHE: dict = {}


def clear_chunk_tune_cache() -> None:
    _CHUNK_TUNE_CACHE.clear()


def probe_chunk(window: int, k: int, n_buckets: int,
                seed: int = 0) -> PacketChunk:
    """Synthetic all-valid (k, window) chunk for timing sweeps: uniform
    bucket ids (realistic scatter conflicts), monotone timestamps,
    in-distribution lengths."""
    rng = np.random.RandomState(seed)
    n = k * window
    shp = (k, window)
    return PacketChunk(
        bucket=jnp.asarray(rng.randint(0, n_buckets, n)
                           .astype(np.int32).reshape(shp)),
        ts=jnp.asarray(np.linspace(0.0, 1.0, n, dtype=np.float32)
                       .reshape(shp)),
        length=jnp.asarray(rng.uniform(60.0, 1500.0, n)
                           .astype(np.float32).reshape(shp)),
        is_fwd=jnp.asarray((rng.rand(n) < 0.5)
                           .astype(np.float32).reshape(shp)),
        valid=jnp.asarray(np.ones(shp, bool)))


def probe_window(window: int, n_buckets: int, seed: int = 0) -> PacketWindow:
    """Synthetic all-valid window (the 1D sibling of ``probe_chunk``),
    shared by the chunk-size autotuner's warmup and the
    ``repro.analysis`` hot-path auditor's tracing probes."""
    c = probe_chunk(window, 1, n_buckets, seed)
    return PacketWindow(bucket=c.bucket[0], ts=c.ts[0], length=c.length[0],
                        is_fwd=c.is_fwd[0], valid=c.valid[0])


def autotune_chunk_windows(make_server, *, window: int, n_buckets: int,
                           candidates=CHUNK_WINDOW_CANDIDATES,
                           default: int = DEFAULT_CHUNK_WINDOWS,
                           candidate_filter=None, reps: int = 3,
                           seed: int = 0, cache_key=None, time_fn=None,
                           verbose: bool = False, events=None) -> int:
    """Measured K sweep at server init: pick ``chunk_windows``.

    ``make_server(k)`` builds a throwaway server compiled for chunk size
    k; each candidate is timed (``kernels.tuning.measure_min`` — warmup
    absorbs compilation) on one synthetic ``probe_chunk`` and scored
    per *packet* so different K compete fairly. The fixed ``default`` is
    always timed too and the winner is the measured argmin over a set
    containing it (``kernels.tuning.sweep_best``), so the sweep can
    never pick a chunk size that regresses versus the default on the
    tuned shape — the same no-tuned-regression contract as the kernel
    tile autotuner. ``candidate_filter`` drops Ks a config cannot use
    (the sharded tier's per-shard backend-slice divisibility); when it
    rejects the default itself, the first surviving candidate takes over
    the default's role. ``time_fn(k) -> seconds`` replaces the
    measurement (deterministic tests); ``cache_key`` memoizes the
    winner per (artifact shape, backend, geometry).

    Timing probes call the real ``backend_fn`` — a *stateful* backend
    (e.g. an injected-fault schedule keyed on call count) will observe
    those extra calls, so combine "auto" with stateless backends or
    pass an explicit chunk_windows.
    """
    if cache_key is not None:
        hit = _CHUNK_TUNE_CACHE.get(cache_key)
        if hit is not None:
            if events is not None:
                events.emit("autotune", knob="chunk_windows", chosen=hit,
                            cached=True)
            return hit
    cands = [k for k in candidates
             if candidate_filter is None or candidate_filter(k)]
    if candidate_filter is not None and not candidate_filter(default):
        if not cands:
            raise ValueError(
                "no chunk_windows candidate satisfies this configuration "
                f"(candidates={tuple(candidates)})")
        default = cands[0]

    def time_k(k: int) -> float:
        if time_fn is not None:
            return float(time_fn(k)) / (k * window)
        srv = make_server(k)
        chunk = probe_chunk(window, k, n_buckets, seed)

        def one():
            pred, _ = srv.step_chunk(chunk)
            jax.block_until_ready(pred)
        return measure_min(one, reps) / (k * window)   # per-packet seconds

    best, _ = sweep_best(cands, time_k, default=default, verbose=verbose,
                         label="chunk-autotune")
    if cache_key is not None:
        _CHUNK_TUNE_CACHE[cache_key] = best
    if events is not None:
        events.emit("autotune", knob="chunk_windows", chosen=best,
                    default=default, candidates=list(cands), cached=False)
    return best


class StreamingHybridServer(HybridServer):
    """HybridServer over a packet stream with per-flow register state.

    window is the static packet chunk size (the compiled step shape);
    n_buckets sizes the flow register file. The batch ``classify`` of the
    parent stays available (tests use it as the one-shot oracle).
    """

    # Declarative contracts the ``repro.analysis`` hot-path auditor keys
    # on: each row names a jitted step attribute, the donate_argnums it
    # is built with (the auditor proves every donated leaf really
    # aliases in the compiled HLO — jax prunes unusable donations
    # silently), and which probe shape traces it. ``collectives`` (set
    # by the sharded tier) pins the exact cross-device census.
    AUDIT_CONTRACTS = (
        {"attr": "_stream_step", "donate": (1, 2), "probe": "window",
         "collectives": {}},
        {"attr": "_stream_switch", "donate": (1,), "probe": "window",
         "collectives": {}},
        {"attr": "_chunk_step", "donate": (1, 2), "probe": "chunk",
         "collectives": {}},
    )

    def __init__(self, artifact: TableArtifact, backend_fn: Callable, *,
                 n_buckets: int = 4096, window: int = 512,
                 threshold: float = 0.7, capacity: int = 64,
                 flush_every: int = 1, chunk_windows: Optional[int] = None,
                 flush_occupancy: Optional[float] = None,
                 flush_deadline: Optional[float] = None,
                 evict_age: Optional[float] = None, saturate: bool = True,
                 evict_policy: str = "timeout", lru_occupancy: float = 0.75,
                 fault_policy: Optional[FaultPolicy] = None,
                 use_pallas: Optional[bool] = None, autotune: bool = False,
                 tiles: Optional[TileConfig] = None,
                 fuse: Optional[bool] = None,
                 obs: Optional[Observability] = None):
        """evict_age: recycle a flow bucket once it has been idle for this
        many (rebased) seconds — the aging sweep runs inside every step
        (``netsim.stream.lifecycle_sweep``) with its cutoff clamped to the
        window's oldest timestamp, so a flow seen in this window survives
        it by construction even when the window spans more than
        evict_age. None disables eviction (bit-exact contract with the
        batch path). saturate keeps the 2^24 overflow
        guard on; clamping is a bitwise no-op below the envelope, so it
        only changes behavior for streams that were already silently
        inexact — now counted in StreamStats.overflow instead.

        flush_every: defer the backend across this many windows
        (DESIGN.md §7). 1 (default) keeps today's one-backend-call-per-
        window behavior bit for bit — the equivalence oracle. k > 1
        accumulates the dispatched low-confidence rows of up to k windows
        in a donated ``DeferredDispatch`` buffer and runs the backend
        once per flush at k-times the occupancy; ``step`` then returns
        *provisional* (switch-tier) predictions and the backend answers
        are back-patched into the pending windows at flush
        (``serve_trace`` consumes the patches and always ends with a
        guaranteed flush, so its predictions are final). Deferred rows'
        features are the register readout of their own window, so final
        predictions match flush_every=1 for any row-wise backend.

        chunk_windows: device-resident chunked streaming (DESIGN.md §8).
        ``serve_trace`` stacks this many windows into one (K, W)
        ``PacketChunk`` transferred once and runs the whole chunk as a
        single jitted ``lax.scan`` megastep — register update, touched-
        flow readout, fused classify and deferral all inside the scan
        with donated carries, the backend exactly once per chunk at the
        boundary (the deferral buffer is the scan carry, so flushes are
        chunk-aligned by construction). Final predictions are
        back-patched before the megastep returns — bit-identical to the
        per-window path for row-wise backends (the oracle tests and
        ``benchmarks/stream_bench.py`` assert). Mutually exclusive with
        flush_every > 1: the chunk IS the flush cycle. Pass the string
        ``"auto"`` to pick K by a measured init-time sweep
        (``autotune_chunk_windows`` — cached per artifact/geometry,
        never a regression versus ``DEFAULT_CHUNK_WINDOWS``).

        flush_occupancy: occupancy-triggered early flush for the
        flush_every > 1 path. A host-side policy (the host already
        tracks the cycle position) flushes the pending cycle as soon as
        the deferral buffer holds at least this fraction of its
        ``flush_every * capacity`` slots, instead of always waiting the
        full cycle — bounding how stale a deferred row can get on
        streams that dispatch at high occupancy, at unchanged final
        predictions (an early flush only splits the cycle). Reading the
        per-window deferred-row count costs one host sync per step, so
        the knob is opt-in; None keeps the fixed cadence (and the
        zero-sync step).

        flush_deadline: deadline-triggered early flush for the
        flush_every > 1 path (the occupancy knob's time-domain twin).
        The host-side cycle tracker latches the earliest timestamp of
        the cycle's first deferred window and flushes as soon as any
        window's newest timestamp is at least this many (rebased)
        seconds past it — bounding how *stale* a deferred row can get
        on sparse streams that never fill the buffer. Same contract as
        flush_occupancy: no recompile (an early flush only splits the
        cycle), bit-identical final predictions, opt-in because reading
        the window timestamps costs one host sync per step.

        evict_policy: "timeout" (default) recycles any bucket idle for
        evict_age seconds; "approx_lru" substitutes the pForest-style
        pressure-triggered sweep (``netsim.stream.approx_lru_sweep``) —
        multi-bit idle-age classes ranked by flow activity, evicting
        only while occupancy exceeds ``lru_occupancy`` and preferring
        oldest-then-smallest flows. Both need evict_age (for approx-LRU
        it is the age-class quantization horizon).

        fault_policy: wrap the backend in a ``serving.faults``
        ``GuardedBackend`` — per-flush timeout, bounded retries with
        exponential backoff, circuit breaker. Forces the two-phase
        serving path (the guard runs on host; bit-identical to fused by
        the equivalence oracle). When a flush ultimately fails the tier
        degrades: dispatched rows keep their provisional switch-tier
        predictions, counted in ``StreamStats.degraded``; with zero
        faults predictions are bit-identical to an unguarded server.

        obs: attach a ``repro.obs.Observability`` — lifecycle events
        (cuts, chunks, flushes, breaker transitions, autotune, drift
        alarms), per-stage timings, metric rollups and drift monitors
        over the serving loop (DESIGN.md §14). None (the default) takes
        no observability branch anywhere and is bit-identical to pre-obs
        serving; with an instance attached, all hooks stay host-side and
        predictions remain bit-identical (the BENCH_obs.json oracle) —
        only the per-``rollup_every`` boundary reads device stats.
        """
        # resolved once (None -> kernels on TPU, references elsewhere) so
        # the auto-K probes and every jitted closure below agree with
        # ``self.use_pallas``
        use_pallas = resolve_use_pallas(use_pallas)
        self._obs = obs
        if obs is not None:
            obs.bind(self)
        if flush_every < 1:
            raise ValueError(f"flush_every must be >= 1, got {flush_every}")
        if chunk_windows == "auto":
            # measured K sweep (never a regression vs the fixed default —
            # see autotune_chunk_windows); resolved before the validation
            # arithmetic below so every downstream check sees an int
            chunk_windows = self._resolve_auto_chunk_windows(
                artifact, backend_fn, n_buckets=n_buckets, window=window,
                threshold=threshold, capacity=capacity,
                evict_age=evict_age, saturate=saturate,
                evict_policy=evict_policy, lru_occupancy=lru_occupancy,
                use_pallas=use_pallas, tiles=tiles, fuse=fuse)
        if chunk_windows is not None:
            if chunk_windows < 1:
                raise ValueError(
                    f"chunk_windows must be >= 1, got {chunk_windows}")
            if flush_every != 1:
                raise ValueError(
                    "chunked streaming aligns backend flushes to chunk "
                    "boundaries (one flush per chunk_windows windows); "
                    "combine it with flush_every=1, not "
                    f"flush_every={flush_every}")
        if flush_occupancy is not None:
            if not 0.0 < flush_occupancy <= 1.0:
                raise ValueError(f"flush_occupancy must be in (0, 1], "
                                 f"got {flush_occupancy}")
            if flush_every == 1:
                raise ValueError("flush_occupancy needs flush_every > 1 "
                                 "(there is no deferral cycle to flush "
                                 "early at flush_every=1)")
        if flush_deadline is not None:
            if flush_deadline <= 0:
                raise ValueError(f"flush_deadline must be > 0, "
                                 f"got {flush_deadline}")
            if flush_every == 1:
                raise ValueError("flush_deadline needs flush_every > 1 "
                                 "(there is no deferral cycle to flush "
                                 "early at flush_every=1)")
        if evict_policy not in EVICT_POLICIES:
            raise ValueError(f"evict_policy must be one of "
                             f"{EVICT_POLICIES}, got {evict_policy!r}")
        if evict_policy == "approx_lru":
            if evict_age is None:
                raise ValueError("evict_policy='approx_lru' needs "
                                 "evict_age (the idle-age quantization "
                                 "horizon of the age classes)")
            if not 0.0 < lru_occupancy < 1.0:
                raise ValueError(f"lru_occupancy must be in (0, 1), "
                                 f"got {lru_occupancy}")
        if fault_policy is not None:
            if fuse:
                raise ValueError("fault_policy guards the host backend "
                                 "call and therefore needs the two-phase "
                                 "serving path; it cannot be combined "
                                 "with fuse=True")
            fuse = False
        super().__init__(artifact, backend_fn, threshold=threshold,
                         capacity=capacity, use_pallas=use_pallas,
                         autotune=autotune, tiles=tiles, fuse=fuse)
        self.n_buckets = n_buckets
        self.window = window
        self.flush_every = flush_every
        self.chunk_windows = chunk_windows
        self.flush_occupancy = flush_occupancy
        self.flush_deadline = flush_deadline
        self.evict_age = evict_age
        self.saturate = saturate
        self.evict_policy = evict_policy
        self.lru_occupancy = lru_occupancy
        self.fault_policy = fault_policy
        self._guard = (GuardedBackend(backend_fn, fault_policy,
                                      events=(obs.events if obs is not None
                                              else None))
                       if fault_policy is not None else None)
        self._state = self._make_state()
        self._stats = StreamStats.zero()
        self._reset_deferred()
        self._ingest = None      # ring telemetry of the last serve_stream
        self._latency = None     # LatencyRecorder of the last serve_stream

        def _switch_half(art, state, w: PacketWindow, threshold):
            """update registers -> aging sweep -> overflow guard -> read
            out touched flows -> classify -> dispatch; shared by the fused
            and two-phase paths. The register half routes through
            ``window_update_readout``: with use_pallas the scatter-update,
            2^24 clamp and touched-row gather fuse into one VMEM pass
            (``kernels.stream_update``), skipping the HBM round-trip
            between them."""
            with jax.named_scope("register_update"):
                state, x, n_ev, n_ov = window_update_readout(
                    state, w, evict_age=evict_age, saturate=saturate,
                    evict_policy=evict_policy, lru_occupancy=lru_occupancy,
                    use_pallas=use_pallas)
            with jax.named_scope("fused_classify"):
                sw_pred, conf = fused_classify(art, x, use_pallas=use_pallas,
                                               tiles=self.tiles)
            fwd = (conf < threshold) & w.valid
            buf, idx, valid = dispatch(x, fwd, capacity)
            return (state, x, sw_pred, fwd, buf, idx, valid, conf,
                    (n_ev, n_ov))

        def stream_step(art, state, stats, w: PacketWindow, threshold):
            (state, x, sw_pred, fwd, buf, idx, valid, conf,
             counts) = _switch_half(art, state, w, threshold)
            be_pred = jnp.asarray(backend_fn(buf))
            stats, pred, frac, rows = accumulate_stream_stats(
                stats, w, sw_pred, be_pred, idx, valid, fwd, conf, *counts)
            return state, stats, pred, frac, rows

        self._stream_step = jax.jit(stream_step, donate_argnums=(1, 2))

        def stream_switch(art, state, w: PacketWindow, threshold):
            (state, x, sw_pred, fwd, buf, idx, valid, conf,
             counts) = _switch_half(art, state, w, threshold)
            return state, sw_pred, fwd, buf, idx, valid, conf, counts

        self._stream_switch = jax.jit(stream_switch, donate_argnums=(1,))

        self._stream_epilogue = jax.jit(accumulate_stream_stats,
                                        donate_argnums=(0,))

        # degraded epilogue: the flush_every=1 two-phase window whose
        # backend flush ultimately failed keeps its switch predictions
        self._degrade_window = jax.jit(degrade_window_stats,
                                       donate_argnums=(0,))

        # -- cross-window deferred dispatch (flush_every > 1) ---------------

        def defer_step(art, state, stats, dd, pending, w, threshold, pos):
            """One window on the deferred path: switch half as above, but
            the dispatched rows go to the deferral buffer instead of the
            backend, and the provisional (switch) predictions land in the
            pending set at cycle slot ``pos`` (traced: no recompiles)."""
            (state, x, sw_pred, fwd, buf, idx, valid, conf,
             counts) = _switch_half(art, state, w, threshold)
            stats, dd, pending, pred, frac, rows = defer_tail(
                stats, dd, pending, w, sw_pred, fwd, buf, idx, valid,
                conf, counts, pos)
            return state, stats, dd, pending, pred, frac, rows

        self._defer_step = jax.jit(defer_step, donate_argnums=(1, 2, 3, 4))

        def flush_fused(stats, dd, pending):
            """Backend over the whole deferral buffer, answers back-patched
            into the pending set; fresh (zeroed) carries come back with
            the patched predictions."""
            be_pred = jnp.asarray(backend_fn(dd.buf))
            patched = backpatch_pending(pending, be_pred, dd)
            stats = fold_flush_stats(stats, dd)
            return (stats, jax.tree.map(jnp.zeros_like, dd), patched,
                    jnp.full_like(pending, -1))

        self._flush_fused = jax.jit(flush_fused, donate_argnums=(0, 1, 2))

        def flush_patch(stats, dd, pending, be_pred):
            """Two-phase flush epilogue: the backend ran on host; patch."""
            patched = backpatch_pending(pending, be_pred, dd)
            stats = fold_flush_stats(stats, dd)
            return (stats, jax.tree.map(jnp.zeros_like, dd), patched,
                    jnp.full_like(pending, -1))

        self._flush_patch = jax.jit(flush_patch, donate_argnums=(0, 1, 2))

        def flush_degraded(stats, dd, pending):
            """Degraded flush: the backend ultimately failed, so the
            pending set — which already holds the provisional switch
            predictions — comes back *unpatched* as the flush result;
            the cycle's rows fold into ``degraded``. ``pending`` is not
            donated: it is returned as-is."""
            stats = fold_degraded_flush(stats, dd)
            return (stats, jax.tree.map(jnp.zeros_like, dd), pending,
                    jnp.full_like(pending, -1))

        self._flush_degraded = jax.jit(flush_degraded,
                                       donate_argnums=(0, 1))

        # -- device-resident chunked streaming (chunk_windows) --------------

        def chunk_switch(art, state, stats, chunk: PacketChunk, threshold):
            """K windows as ONE device program, sequential only where the
            data is: ``chunk_update_readout`` carries the register file
            through the K scatter-update + touched-row-gather steps (a
            lax.scan over the packed register file; the Pallas kernel
            per step on TPU), stacking the (K, W, 8) readout rows.
            Everything row-wise then runs ONCE over the whole chunk —
            fused classify on K*W rows, vmapped capacity-bounded
            dispatch, the stats fold — instead of K small sequential
            passes; the batched composition is bit-identical because
            every per-row op is row-independent."""
            with jax.named_scope("register_scan"):
                state, xs, n_ev, n_ov = chunk_update_readout(
                    state, chunk, evict_age=evict_age, saturate=saturate,
                    evict_policy=evict_policy, lru_occupancy=lru_occupancy,
                    use_pallas=use_pallas)
            stats, dd, pending, frac, rows = chunk_classify_tail(
                art, stats, chunk, xs, n_ev, n_ov, threshold, capacity,
                use_pallas=use_pallas, tiles=self.tiles)
            return state, stats, dd, pending, frac, rows

        self._chunk_switch = jax.jit(chunk_switch, donate_argnums=(1, 2))

        def chunk_step(art, state, stats, chunk: PacketChunk, threshold):
            """The whole megastep as one device dispatch: scan + batched
            switch half, backend ONCE over the chunk's deferred rows,
            back-patch — returning *final* predictions."""
            state, stats, dd, pending, frac, rows = chunk_switch(
                art, state, stats, chunk, threshold)
            be_pred = jnp.asarray(backend_fn(dd.buf))
            patched = backpatch_pending(pending, be_pred, dd)
            return state, stats, patched, frac, rows

        self._chunk_step = jax.jit(chunk_step, donate_argnums=(1, 2))

        def chunk_patch(pending, be_pred, dd):
            """Two-phase chunk epilogue: the backend ran on host; patch."""
            return backpatch_pending(pending, be_pred, dd)

        self._chunk_patch = jax.jit(chunk_patch, donate_argnums=(0,))

        self._degrade_chunk = jax.jit(degrade_chunk_stats,
                                      donate_argnums=(0,))

    # -- streaming state ----------------------------------------------------

    def _make_state(self):
        """Fresh register file — the state-layout hook subclasses override
        (the sharded tier allocates its mesh-placed table here instead of
        a dead single-device one)."""
        return init_flow_table(self.n_buckets)

    def _make_deferred(self) -> DeferredDispatch:
        """Fresh deferral buffer — the sharded tier overrides with its
        per-shard partial-row layout."""
        return init_deferred(self.flush_every, self.capacity, FLOW_FEATURES)

    def _reset_deferred(self):
        """Empty pending cycle: deferral buffer, per-window pending
        prediction set, and the host-side cycle position / occupancy
        count. (The chunked path carries no deferral state between
        megasteps — its DeferredDispatch lives and dies inside one
        chunk.)"""
        self._pending_n = 0
        self._occ_rows = 0
        self._cycle_born = None
        self._flush_queue = []
        if self.flush_every > 1:
            self._dd = self._make_deferred()
            self._pending = jnp.full((self.flush_every, self.window), -1,
                                     jnp.int32)
        else:
            self._dd = self._pending = None

    @property
    def state(self) -> FlowTableState:
        """Current register file. Donated into every step: read, don't keep."""
        return self._state

    @property
    def stats(self) -> StreamStats:
        return self._stats

    @property
    def pending_windows(self) -> int:
        """Windows deferred in the current (unflushed) cycle."""
        return self._pending_n

    @property
    def fault_stats(self) -> Optional[FaultStats]:
        """Host-side telemetry of the fault-policy guard (None without a
        ``fault_policy``): attempts, retries, timeouts, breaker
        transitions — see ``serving.faults.FaultStats``."""
        return self._guard.stats if self._guard is not None else None

    @property
    def ingest_stats(self):
        """``netsim.ingest.IngestStats`` of the most recent (or running)
        ``serve_stream`` — admitted/dropped packets, count vs deadline vs
        drain cuts. None before the first serve_stream."""
        return self._ingest

    @property
    def latency(self) -> Optional[LatencyRecorder]:
        """Admit->prediction LatencyRecorder of the most recent
        ``serve_stream(record_latency=True)``; ``.summary()`` gives the
        p50/p95/p99 row. None otherwise."""
        return self._latency

    # -- chunk-size autotune hooks ------------------------------------------

    def _auto_chunk_server(self, k: int, artifact, backend_fn, **kw):
        """Throwaway same-tier server compiled for chunk size k — the
        sweep's timing target. The sharded tier overrides to pin its
        mesh. fault_policy is deliberately not forwarded: probe timings
        should measure the serving path, not retry/backoff schedules
        (and "auto" is documented as a stateless-backend knob)."""
        return StreamingHybridServer(artifact, backend_fn,
                                     chunk_windows=k, **kw)

    def _auto_chunk_filter(self, capacity: int):
        """Candidate predicate (None = all Ks valid); the sharded tier
        restricts to Ks whose deferral buffer divides over the mesh."""
        return None

    def _resolve_auto_chunk_windows(self, artifact, backend_fn, *,
                                    n_buckets, window, capacity,
                                    **kw) -> int:
        key = (type(self).__name__, getattr(self, "n_shards", 1),
               _artifact_key(artifact), id(backend_fn),
               jax.default_backend(), window, n_buckets, capacity)
        return autotune_chunk_windows(
            lambda k: self._auto_chunk_server(
                k, artifact, backend_fn, n_buckets=n_buckets,
                window=window, capacity=capacity, **kw),
            window=window, n_buckets=n_buckets,
            candidate_filter=self._auto_chunk_filter(capacity),
            cache_key=key,
            events=(self._obs.events if self._obs is not None else None))

    def _host_backend(self, rows):
        """The two-phase host backend invocation, fault-guarded when a
        policy is set. Returns the backend's predictions, or None when
        the flush ultimately failed and the caller must degrade (keep
        provisional switch predictions, fold into ``degraded``). With an
        Observability attached the call is timed as the
        ``backend_flush`` stage."""
        obs = self._obs
        if obs is None:
            if self._guard is None:
                return self.backend_fn(rows)
            return self._guard(rows)
        with obs.stage("backend_flush"):
            if self._guard is None:
                return self.backend_fn(rows)
            return self._guard(rows)

    def flow_table(self) -> jax.Array:
        """(n_buckets, 8) feature table from the current registers."""
        return flow_table_readout(self._state)

    def reset(self):
        """Fresh register file + telemetry (a new stream epoch). Any
        pending deferred windows are dropped unflushed — flush() first if
        their backend answers matter."""
        self._state = self._make_state()
        self._stats = StreamStats.zero()
        self._reset_deferred()
        if self._guard is not None:
            self._guard.reset()

    # -- serving ------------------------------------------------------------

    def step(self, w: PacketWindow):
        """Serve one window. -> (pred (W,), HybridStats for this window).

        Single device dispatch on the fused path; pad lanes report -1.
        Fully async — nothing here blocks on the device.

        With flush_every > 1 the returned predictions are *provisional*:
        deferred rows carry the low-confidence switch answer until the
        cycle flushes (automatically every flush_every windows, or on an
        explicit ``flush()``), at which point the back-patched final
        predictions for the whole cycle are available from
        ``consume_flush()``. ``HybridStats.backend_rows`` reports the
        rows *deferred* this window (they reach the backend at flush).

        NOT retry-safe: the register file advances (and the old state is
        donated) before the backend runs, so on the two-phase path a
        backend exception leaves the window already folded in — calling
        step(w) again double-counts it. Recover by reset() or by skipping
        the failed window, never by replaying it.
        """
        tau = jnp.float32(self.threshold)
        if self.flush_every == 1:
            if self._fused_ok is None:
                try:
                    self._state, self._stats, pred, frac, rows = \
                        self._stream_step(self.artifact, self._state,
                                          self._stats, w, tau)
                    self._fused_ok = True
                    return pred, HybridStats(frac, rows, self.capacity)
                except (jax.errors.JAXTypeError, TypeError):
                    # tracing failed before execution: neither the state
                    # nor the stats carry was consumed by the donation
                    self._fused_ok = False
            if self._fused_ok:
                self._state, self._stats, pred, frac, rows = \
                    self._stream_step(self.artifact, self._state,
                                      self._stats, w, tau)
                return pred, HybridStats(frac, rows, self.capacity)
            (self._state, sw_pred, fwd, buf, idx, valid, conf,
             counts) = self._stream_switch(self.artifact, self._state, w,
                                           tau)
            be = self._host_backend(buf)
            if be is None:          # flush failed: degrade to switch-only
                self._stats, pred, frac, rows = self._degrade_window(
                    self._stats, w, sw_pred, fwd, valid, conf, *counts)
                return pred, HybridStats(frac, rows, self.capacity)
            self._stats, pred, frac, rows = self._stream_epilogue(
                self._stats, w, sw_pred, jnp.asarray(be), idx, valid, fwd,
                conf, *counts)
            return pred, HybridStats(frac, rows, self.capacity)
        # deferred path: no backend here — defer, auto-flush when full
        (self._state, self._stats, self._dd, self._pending, pred, frac,
         rows) = self._defer_step(self.artifact, self._state, self._stats,
                                  self._dd, self._pending, w, tau,
                                  jnp.int32(self._pending_n))
        self._pending_n += 1
        full = self._pending_n >= self.flush_every
        trigger = "cycle_full"
        if self.flush_occupancy is not None and not full:
            # occupancy-triggered early flush: reading the deferred-row
            # count costs one host sync — the knob is opt-in (see __init__)
            self._occ_rows += int(rows)
            if self._occ_rows >= self.flush_occupancy * self._dd.slots:
                full = True
                trigger = "occupancy"
        if self.flush_deadline is not None:
            # deadline-triggered early flush: age the oldest pending
            # window (earliest ts latched at cycle start) against this
            # window's newest timestamp — one host sync, opt-in
            ts = np.asarray(w.ts)[np.asarray(w.valid)]
            if ts.size:
                if self._cycle_born is None:
                    self._cycle_born = float(ts.min())
                if (not full and float(ts.max()) - self._cycle_born
                        >= self.flush_deadline):
                    full = True
                    trigger = "deadline"
        if full:
            # queued, not overwritten: a manual caller who steps through
            # several cycles without consuming loses nothing
            self._flush_queue.append(self.flush(trigger=trigger))
        return pred, HybridStats(frac, rows, self.capacity)

    # -- deferred-dispatch flushing -----------------------------------------

    def _flush_rows_host(self, dd: Optional[DeferredDispatch] = None):
        """Complete deferred rows for a host (two-phase) backend call.
        The sharded buffer holds per-shard partial rows (non-owner lanes
        exactly zero), so summing the shard dim reconstructs them."""
        buf = np.asarray((dd or self._dd).buf)
        return buf.sum(axis=0, dtype=np.float32) if buf.ndim == 3 else buf

    def flush(self, *, trigger: str = "manual"):
        """Run the backend on the pending deferral cycle and back-patch.

        -> (n_windows_flushed, patched (flush_every, W) predictions) with
        the flushed windows at rows [0, n); None when nothing is pending
        (or flush_every == 1, where every step already ran the backend).
        ``serve_trace`` calls this at trace end — the guaranteed flush —
        and after every auto-flush; drive it yourself when stepping
        manually. The deferral buffer and pending set are consumed
        (donated) and replaced by fresh zeroed carries. ``trigger``
        labels the lifecycle event when an Observability is attached
        ("cycle_full" / "occupancy" / "deadline" / "end_of_stream" /
        "manual") — it never changes behavior.
        """
        if self.flush_every == 1 or self._pending_n == 0:
            return None
        n = self._pending_n
        obs = self._obs
        if obs is not None:
            obs.emit("flush", windows=n, trigger=trigger)
        if self._fused_ok is None:
            try:
                self._stats, self._dd, patched, self._pending = \
                    self._flush_fused(self._stats, self._dd, self._pending)
                self._fused_ok = True
                self._pending_n = 0
                self._occ_rows = 0
                self._cycle_born = None
                if obs is not None:
                    obs.emit("backpatch", windows=n)
                return n, patched
            except (jax.errors.JAXTypeError, TypeError):
                # tracing failed before execution: nothing was donated
                self._fused_ok = False
        if self._fused_ok:
            self._stats, self._dd, patched, self._pending = \
                self._flush_fused(self._stats, self._dd, self._pending)
            if obs is not None:
                obs.emit("backpatch", windows=n)
        else:
            be = self._host_backend(self._flush_rows_host())
            if be is None:      # flush failed: keep provisional answers
                self._stats, self._dd, patched, self._pending = \
                    self._flush_degraded(self._stats, self._dd,
                                         self._pending)
                if obs is not None:
                    obs.emit("degraded", windows=n)
            else:
                if obs is not None:
                    with obs.stage("backpatch"):
                        (self._stats, self._dd, patched,
                         self._pending) = self._flush_patch(
                            self._stats, self._dd, self._pending,
                            jnp.asarray(be))
                    obs.emit("backpatch", windows=n)
                else:
                    self._stats, self._dd, patched, self._pending = \
                        self._flush_patch(self._stats, self._dd,
                                          self._pending, jnp.asarray(be))
        self._pending_n = 0
        self._occ_rows = 0
        self._cycle_born = None
        return n, patched

    def consume_flush(self):
        """Pop the oldest unconsumed auto-flush result (or None): the
        (n_windows, patched predictions) pair ``step`` queued when a
        cycle filled. FIFO, so stepping through several cycles before
        consuming loses nothing."""
        return self._flush_queue.pop(0) if self._flush_queue else None

    # -- chunked serving -----------------------------------------------------

    def step_chunk(self, chunk: PacketChunk):
        """Serve K stacked windows as ONE device dispatch.
        -> (pred (K, W), HybridStats for the chunk).

        The megastep scans the chunk's windows through the switch half
        with donated carries (register file, stats, deferral buffer),
        runs the backend exactly once over the chunk's deferred rows,
        and back-patches — the returned predictions are *final* (not
        provisional), with pad/dead lanes at -1. Requires
        ``chunk_windows`` (the compiled scan length); chunks must have
        exactly that many window rows (``iter_chunks`` pads the ragged
        final chunk with dead windows). Same retry discipline as
        ``step``: the state advances before a two-phase backend runs,
        so never replay a failed chunk.
        """
        if self.chunk_windows is None:
            raise ValueError("server built without chunk_windows")
        if chunk.n_windows != self.chunk_windows:
            raise ValueError(f"chunk has {chunk.n_windows} windows, server "
                             f"compiled for {self.chunk_windows}")
        if chunk.window != self.window:
            raise ValueError(f"chunk windows are {chunk.window} lanes wide, "
                             f"server compiled for {self.window}")
        tau = jnp.float32(self.threshold)
        if self._fused_ok is None:
            try:
                self._state, self._stats, patched, frac, rows = \
                    self._chunk_step(self.artifact, self._state,
                                     self._stats, chunk, tau)
                self._fused_ok = True
                return patched, HybridStats(frac, rows, self.capacity)
            except (jax.errors.JAXTypeError, TypeError):
                # tracing failed before execution: nothing was donated
                self._fused_ok = False
        if self._fused_ok:
            self._state, self._stats, patched, frac, rows = \
                self._chunk_step(self.artifact, self._state, self._stats,
                                 chunk, tau)
            return patched, HybridStats(frac, rows, self.capacity)
        # two-phase: jitted switch half, host backend, jitted back-patch
        self._state, self._stats, dd, pending, frac, rows = \
            self._chunk_switch(self.artifact, self._state, self._stats,
                               chunk, tau)
        be = self._host_backend(self._flush_rows_host(dd))
        obs = self._obs
        if be is None:          # flush failed: provisional set unpatched,
            #                     retract the optimistic in-graph fold
            self._stats = self._degrade_chunk(self._stats, dd)
            if obs is not None:
                obs.emit("degraded", windows=chunk.n_windows)
            return pending, HybridStats(frac, rows, self.capacity)
        if obs is not None:
            with obs.stage("backpatch"):
                patched = self._chunk_patch(pending, jnp.asarray(be), dd)
            obs.emit("backpatch", windows=chunk.n_windows)
        else:
            patched = self._chunk_patch(pending, jnp.asarray(be), dd)
        return patched, HybridStats(frac, rows, self.capacity)

    # -- open-ended serving --------------------------------------------------

    def serve_stream(self, source, *, t0: Optional[float] = None,
                     deadline: Optional[float] = None,
                     ring_capacity: Optional[int] = None,
                     prefetch: Optional[bool] = None,
                     prefetch_depth: int = 2,
                     record_latency: bool = False,
                     latency_samples: Optional[int] = None,
                     clock: Callable[[], float] = time.monotonic):
        """The primary serving loop: pull packets from an open-ended
        ``source`` through the ingest ring. -> (pred (P,), stats).

        ``source`` is any iterable of PacketTrace batches (a live
        capture adapter, ``netsim.ingest.replay_source`` for finite
        traces, a generator pacing a scenario). Batches are admitted
        into a ``PacketRingBuffer`` and cut into window-granular chunks
        by count or ``deadline`` (wall seconds an admitted packet may
        wait), whichever fires first — see ``netsim.ingest``. Because
        cuts never move window boundaries, predictions, the flow table
        and every StreamStats field except ``flushes`` are bit-identical
        under ANY cut grouping; replaying a finite trace in one batch
        reproduces the offline grouping exactly (``serve_trace``'s
        contract, oracle-gated by tests/test_ingest.py).

        Ingest is pull-based, so backpressure is "the source waits":
        nothing is dropped, ``ring_capacity`` (default 4 chunks) bounds
        host memory. Push-style admission with tail-drop is the ring's
        own ``drop=True`` mode, not this loop.

        On the chunked path (``chunk_windows`` set) ``prefetch`` (default
        on) maps cuts to device chunks on a background thread with a
        bounded ``prefetch_depth`` queue — chunk k+1's (K, W) transfer
        is in flight while chunk k runs in the scan megastep. The
        per-window path has no chunk transfer to overlap: prefetch=True
        there is a configuration error (ValueError); the default (None)
        auto-disables.

        record_latency=True records every packet's admit->prediction
        wall latency into ``self.latency`` (p50/p95/p99 via
        ``.summary()``) — *final*-prediction semantics: a chunk's
        packets complete when the megastep's back-patched predictions
        are host-visible; under deferred dispatch (flush_every > 1) a
        window's packets complete at the flush that back-patches its
        cycle (deferred rows' extra wait is therefore included). The
        required per-cut host sync costs throughput, so the knob is
        opt-in; off keeps the zero-sync loop. ``latency_samples`` bounds
        the recorder's memory with a seeded reservoir (exact mean/max,
        sampled percentiles) — None keeps exact percentiles at unbounded
        memory, the right default for finite traces; open-ended streams
        should set it (see ``netsim.ingest.LatencyRecorder``).

        With an ``obs=Observability`` attached at construction, this
        loop emits lifecycle events (serve_begin/cut/chunk/window/
        flush/rollup/serve_end), times pipeline stages, closes a metric
        rollup window every ``rollup_every`` dispatches (the loop's only
        device-stats read), and feeds the drift monitors; each stage is
        also a span on the profiler's clock. Predictions, flow table, and
        StreamStats stay bit-identical with obs attached (oracle-gated
        in tests and benchmarks/obs_bench.py).

        Composition with the flush knobs (documented precedence): the
        ingest ``deadline`` acts in the *wall-clock* domain on admitted
        packets and only changes cut grouping; ``flush_deadline`` /
        ``flush_occupancy`` act in the *data-time / occupancy* domain on
        the deferral cycle inside ``step`` and only change flush
        grouping. They compose freely (flush knobs require
        flush_every > 1, which excludes the chunked path, so at most one
        of {chunk prefetch, flush knobs} is ever active); when a count
        cut and a deadline cut are both due, the count cut wins.
        ``self.ingest_stats`` reports admitted/dropped/cut telemetry.
        """
        chunked = bool(self.chunk_windows)
        if prefetch is None:
            prefetch = chunked
        if prefetch and not chunked:
            raise ValueError(
                "prefetch double-buffers (K, W) chunk transfers and "
                "needs the chunked path — build the server with "
                "chunk_windows (prefetch=None auto-disables on the "
                "per-window path)")
        ring = PacketRingBuffer(self.window,
                                self.chunk_windows if chunked else 1,
                                self.n_buckets, t0=t0,
                                capacity=ring_capacity, deadline=deadline,
                                clock=clock)
        self._ingest = ring.stats
        rec = (LatencyRecorder(max_samples=latency_samples)
               if record_latency else None)
        self._latency = rec
        # windows pending from manual step() calls belong to a different
        # prediction stream: flush them, drop their patches
        self.flush()
        self._flush_queue = []
        preds = []
        cuts = cut_stream(ring, source)
        obs = self._obs
        if obs is not None:
            obs.emit("serve_begin", tier=type(self).__name__,
                     window=self.window,
                     chunk_windows=self.chunk_windows or 0,
                     flush_every=self.flush_every, prefetch=bool(prefetch))
            obs.reset_ticks()
            # the rollup baseline: ONE stats read before the loop, so
            # boundary deltas are exact even on a warm server
            obs_prev = self._stats.as_dict()
            obs_b0 = 0                # preds index of the last boundary

        def _done(x) -> float:
            jax.block_until_ready(x)
            return clock()

        if chunked:
            def make_pairs():
                # generator (not genexpr) so the obs stage timers can
                # bracket the cut pull and the H2D map separately; with
                # prefetch on, both run on the prefetch thread and the
                # timings measure producer-side durations
                it = iter(cuts)
                while True:
                    try:
                        if obs is not None:
                            with obs.stage("ring_cut"):
                                c = next(it)
                        else:
                            c = next(it)
                    except StopIteration:
                        return
                    if obs is not None:
                        with obs.stage("h2d"):
                            ch = c.to_chunk()
                    else:
                        ch = c.to_chunk()
                    yield c, ch

            pairs = make_pairs()
            if prefetch:
                pairs = prefetch_iter(pairs, depth=prefetch_depth)
            for cut, chunk in pairs:
                if obs is not None:
                    obs.emit("cut", cut_kind=cut.kind, packets=cut.n,
                             windows=cut.n_windows)
                    with obs.stage("megastep"):
                        pred, _ = self.step_chunk(chunk)
                else:
                    pred, _ = self.step_chunk(chunk)
                flat = pred.reshape(-1)[:cut.n]   # live rows lead; pad/-1
                #                                   lanes only trail them
                if rec is not None:
                    rec.record(cut.admit_time, _done(flat))
                preds.append(flat)
                if obs is not None:
                    obs.emit("chunk", windows=cut.n_windows, packets=cut.n)
                    if obs.tick():
                        obs_prev, obs_b0 = self._obs_rollup(
                            obs, preds, obs_b0, obs_prev,
                            n_dispatches=obs.config.rollup_every,
                            collapse=True)
            if obs is not None and obs.pending_ticks:
                obs_prev, obs_b0 = self._obs_rollup(
                    obs, preds, obs_b0, obs_prev,
                    n_dispatches=obs.pending_ticks, collapse=True)
            flat = (np.concatenate([np.asarray(p) for p in preds])
                    if preds else np.zeros((0,), np.int32))
            if obs is not None:
                obs.emit("serve_end", packets=int(flat.size),
                         cuts=ring.stats.cuts,
                         windows=self._stats.n_windows)
            return jnp.asarray(flat), self._stats.check()

        # per-window path (incl. deferred dispatch); one window per cut
        times = []                    # admit times aligned with preds
        n_live = 0

        def _patch(fl):
            k, patched = fl
            preds[-k:] = [patched[i] for i in range(k)]
            if rec is not None:
                done = _done(patched)
                for at in times[len(times) - k:]:
                    rec.record(at, done)

        for cut in cuts:
            if obs is not None:
                obs.emit("cut", cut_kind=cut.kind, packets=cut.n,
                         windows=cut.n_windows)
            for w in cut.to_windows():
                if obs is not None:
                    with obs.stage("megastep"):
                        pred, _ = self.step(w)
                else:
                    pred, _ = self.step(w)
                preds.append(pred)
                times.append(cut.admit_time)
                n_live += cut.n
                if rec is not None and self.flush_every == 1:
                    rec.record(cut.admit_time, _done(pred))
                fl = self.consume_flush()
                if fl is not None:
                    _patch(fl)
                if obs is not None:
                    obs.emit("window", packets=cut.n)
                    if obs.tick():
                        # never collapse: _patch slices preds per window
                        obs_prev, obs_b0 = self._obs_rollup(
                            obs, preds, obs_b0, obs_prev,
                            n_dispatches=obs.config.rollup_every,
                            collapse=False)
        fl = self.flush(trigger="end_of_stream")   # guaranteed final flush
        if fl is not None:
            _patch(fl)
        if obs is not None and obs.pending_ticks:
            obs_prev, obs_b0 = self._obs_rollup(
                obs, preds, obs_b0, obs_prev,
                n_dispatches=obs.pending_ticks, collapse=False)
        flat = (np.concatenate([np.asarray(p) for p in preds])[:n_live]
                if preds else np.zeros((0,), np.int32))
        if obs is not None:
            obs.emit("serve_end", packets=n_live, cuts=ring.stats.cuts,
                     windows=self._stats.n_windows)
        return jnp.asarray(flat), self._stats.check()

    def _obs_rollup(self, obs, preds, b0, prev, *, n_dispatches, collapse):
        """Close one observability rollup window at a dispatch boundary.

        The loop's ONE device read per ``rollup_every`` dispatches: a
        StreamStats snapshot whose delta against the previous boundary
        is the rollup sample (all additive counters), plus the predicted
        class counts of the predictions emitted since the last boundary
        (pad/-1 lanes excluded; on the deferred per-window path these
        may still be provisional — the class-mix signal tolerates that).
        ``collapse=True`` (chunked path only) replaces the consumed
        preds entries with their host concatenation so the end-of-stream
        concat does no second device->host conversion; the per-window
        path must keep one entry per window for the flush back-patch.
        An eviction-sweep delta surfaces as an ``eviction`` event.
        Returns (snapshot, new_b0) for the next boundary."""
        cur = self._stats.as_dict()
        delta = {k: cur[k] - prev[k]
                 for k in ("windows", "packets", "handled", "backend_rows",
                           "deferred", "degraded", "flushes", "evicted",
                           "overflow", "conf_sum")}
        if len(preds) > b0:
            seg = np.concatenate([np.asarray(p).reshape(-1)
                                  for p in preds[b0:]])
            if collapse:
                preds[b0:] = [seg]
        else:
            seg = np.zeros(0, np.int32)
        live = seg[seg >= 0]
        counts = np.bincount(live, minlength=self.artifact.n_classes)
        if delta["evicted"] > 0:
            obs.emit("eviction", buckets=int(delta["evicted"]))
        sample = dict(delta, dispatches=int(n_dispatches),
                      class_counts=counts.tolist())
        obs.observe_rollup(sample)
        return cur, len(preds)

    def serve_trace(self, trace, *, t0: Optional[float] = None):
        """Stream a whole PacketTrace. -> (pred (P,), stats).

        A thin finite-replay wrapper over ``serve_stream``: the trace
        enters the ingest ring as one batch, so t0 latches to the trace
        minimum (the offline iterators' epoch), every cut is a count cut
        and the grouping — hence predictions, flow table and StreamStats
        including ``flushes`` — is bit-identical to driving
        ``iter_chunks``/``iter_windows`` through ``step_chunk``/``step``
        directly (the pre-refactor loop; tests/test_ingest.py keeps the
        oracle). Per-packet predictions return concatenated in arrival
        order with pad lanes stripped; under deferred dispatch they are
        final (every cycle back-patched, trailing cycle flushed).
        Prefetch is left at its default (on for the chunked path).
        """
        return self.serve_stream(replay_source(trace), t0=t0)
