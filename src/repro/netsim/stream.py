"""Streaming flow-table tier: per-flow registers updated window by window.

The paper's challenge (ii) is extracting features *on the data plane*,
where packets arrive continuously and per-flow registers are updated
incrementally — a switch never sees the whole trace at once. This module
is that deployment shape (pForest's per-flow state across packet windows):

  register file   -> ``FlowTableState``: one array per switch register
                     (pkt/byte counts, first/last ts, fwd/rev splits)
  per-packet ALU  -> ``update_flow_table``: segment-scatter ops folding a
                     ``PacketWindow`` into the registers, jit/donation
                     friendly (all-array dataclasses)
  register readout-> ``flow_table_readout``: derives the same 8 feature
                     columns as the one-shot ``features.flow_features``
  recirculation   -> ``iter_windows``: chunks a PacketTrace into
                     fixed-size packet windows (tile-padded via
                     ``kernels.ops.pad_window`` so shapes stay static)

Bit-consistency contract (asserted by tests and the stream benchmark):
streaming over W windows reproduces the batch ``flow_features`` table on
the concatenated trace *bit for bit*, because

  * count/byte registers are integer-valued f32 sums — exact in any
    association order while magnitudes stay below 2^24 (≈16.7 MB per
    bucket; an eviction/aging policy is the ROADMAP follow-on);
  * first/last-timestamp registers are min/max — associative and exact;
  * duration / mean-IAT are *derived at readout* through the shared
    ``features.table_from_registers``, never accumulated.

Timestamps are rebased to the stream epoch ``t0`` in float64 before the
f32 cast, matching ``features.rebase_ts``. ``t0`` defaults to the trace's
*minimum* timestamp (the batch path's epoch), not the first packet seen —
a reordered first window would otherwise silently shift every rebased
value by the f32 rounding of a different base. Callers serving an
open-ended stream (who cannot pre-scan for the minimum) pass an explicit
provisional ``t0``; the sharded tier additionally carries the true epoch
as a min-merged register (``shard_stream``) so a mis-latched base is
corrected at readout.

Flow lifecycle (pForest-style aging) lives in the same register file:
``age_out`` resets buckets idle since before a cutoff back to the init
identities (via the masked-scatter ``kernels.ops.evict_fill``), and
``saturate_counts`` clamps count/byte registers at the 2^24 f32
integer-exactness envelope, returning a telemetry count so envelope
violations are visible instead of silently inexact.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import evict_fill, pad_window
from repro.kernels.tuning import resolve_use_pallas
from repro.netsim.features import (fnv1a_hash, rebase_ts_np,
                                   table_from_registers)

FLOW_FEATURES = 8      # columns of the readout table == features.flow_features

# f32 integer-exactness envelope: count/byte registers are integer-valued
# f32 sums, exact only below 2^24. saturate_counts clamps here.
OVERFLOW_LIMIT = float(1 << 24)

# per-register init/evict identities, in FlowTableState field order
REGISTER_FIELDS = ("pkt_count", "byte_count", "t_min", "t_max",
                   "fwd_pkts", "rev_pkts", "fwd_bytes", "rev_bytes")
EVICT_FILLS = (0.0, 0.0, float("inf"), float("-inf"), 0.0, 0.0, 0.0, 0.0)
# registers under the 2^24 envelope (monotone f32 integer accumulators)
COUNT_FIELDS = ("pkt_count", "byte_count", "fwd_pkts", "rev_pkts",
                "fwd_bytes", "rev_bytes")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FlowTableState:
    """Register-file carry: one (n_buckets,) f32 array per switch register.

    t_min/t_max start at the segment_min/max identities (±inf) so an
    untouched bucket reads out exactly like one the batch path never saw.
    """
    pkt_count: jax.Array
    byte_count: jax.Array
    t_min: jax.Array
    t_max: jax.Array
    fwd_pkts: jax.Array
    rev_pkts: jax.Array
    fwd_bytes: jax.Array
    rev_bytes: jax.Array

    @property
    def n_buckets(self) -> int:
        return self.pkt_count.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PacketWindow:
    """One fixed-size chunk of the packet stream, ready for the jitted step.

    ts is rebased f32 (see module docstring); is_fwd is 1.0 for forward
    direction; valid masks tile-pad lanes out of every register update.
    """
    bucket: jax.Array    # (W,) int32 flow-hash bucket ids
    ts: jax.Array        # (W,) f32 rebased seconds
    length: jax.Array    # (W,) f32 packet bytes
    is_fwd: jax.Array    # (W,) f32 1.0 = forward
    valid: jax.Array     # (W,) bool

    @property
    def size(self) -> int:
        return self.bucket.shape[0]


def init_flow_table(n_buckets: int) -> FlowTableState:
    # distinct buffers per register: donated steps may not alias arguments
    z = lambda: jnp.zeros((n_buckets,), jnp.float32)
    return FlowTableState(
        pkt_count=z(), byte_count=z(),
        t_min=jnp.full((n_buckets,), jnp.inf, jnp.float32),
        t_max=jnp.full((n_buckets,), -jnp.inf, jnp.float32),
        fwd_pkts=z(), rev_pkts=z(), fwd_bytes=z(), rev_bytes=z())


def update_flow_table(state: FlowTableState,
                      window: PacketWindow) -> FlowTableState:
    """Fold one window into the register file (pure; jit/donation safe).

    Sums ride masked scatter-adds *into the carry* (``.at[b].add``: an
    invalid lane adds exactly 0.0 — a bitwise no-op on the non-negative
    count registers); first/last ts ride scatter-min/max with invalid
    lanes pinned to the reduction identity. Under donation the scatters
    update the carried buffers in place — no per-window materialization
    of ``n_buckets``-sized temporaries, which dominated the old
    segment_sum formulation (zeroed (n_buckets,) output + full-array add
    per register, 8x per window). Bit-identical to the batch segment
    reductions in any association order while the registers stay in the
    integer-exactness envelope (counts below 2^24; min/max are exact
    always) — the same contract the streaming tier already documents.
    """
    b = window.bucket
    w = window.valid.astype(jnp.float32)
    inf = jnp.float32(jnp.inf)
    ln, fwd = window.length, window.is_fwd
    return FlowTableState(
        pkt_count=state.pkt_count.at[b].add(w),
        byte_count=state.byte_count.at[b].add(ln * w),
        t_min=state.t_min.at[b].min(jnp.where(window.valid, window.ts, inf)),
        t_max=state.t_max.at[b].max(jnp.where(window.valid, window.ts,
                                              -inf)),
        fwd_pkts=state.fwd_pkts.at[b].add(fwd * w),
        rev_pkts=state.rev_pkts.at[b].add((1.0 - fwd) * w),
        fwd_bytes=state.fwd_bytes.at[b].add(ln * fwd * w),
        rev_bytes=state.rev_bytes.at[b].add(ln * (1.0 - fwd) * w))


def age_out(state: FlowTableState, evict_before,
            *, use_pallas=None) -> tuple:
    """LRU/timeout eviction sweep: recycle buckets idle too long.

    A bucket whose last-seen timestamp (t_max) predates ``evict_before``
    is reset to the init identities — bit-identical to a bucket the
    stream never touched, so an evicted-then-reborn flow reads out
    exactly like a fresh one (``table_from_registers`` cannot tell them
    apart; tests assert this). Surviving buckets pass through untouched
    bit for bit. Returns (state, n_evicted i32).

    The reset rides ``kernels.ops.evict_fill`` — a masked scatter over
    the stacked register file (Pallas on TPU, jnp.where elsewhere) — so
    the sweep folds into the same jitted step as the window update.
    """
    evict = (state.pkt_count > 0) & (state.t_max
                                     < jnp.float32(evict_before))
    regs = jnp.stack([getattr(state, f) for f in REGISTER_FIELDS])
    fills = jnp.asarray(EVICT_FILLS, jnp.float32)
    out = evict_fill(regs, evict, fills, use_pallas=use_pallas)
    new = FlowTableState(**{f: out[i]
                            for i, f in enumerate(REGISTER_FIELDS)})
    return new, jnp.sum(evict.astype(jnp.int32))


def saturate_counts(state: FlowTableState, *, limit: float = OVERFLOW_LIMIT,
                    prev: Optional[FlowTableState] = None) -> tuple:
    """Overflow guard for the f32 integer-exactness envelope.

    Count/byte registers are integer-valued f32 accumulators — exact
    below 2^24, silently lossy above. Clamping at the limit is a bitwise
    no-op for every in-envelope register, so the guard can stay on in
    serving paths without perturbing the streaming-vs-batch equality;
    the returned i32 counts register slots *newly* saturated by this
    sweep (cumulative in ``StreamStats.overflow``), so the telemetry
    grows once per saturation event rather than re-counting every
    already-clamped slot each window (which inflated linearly with
    stream length). Returns (state, n_newly_saturated).

    ``prev`` is the register file at the start of the window (before
    ``update_flow_table``): a slot counts iff it reached the limit now
    but was below it then — exactly once per saturation event. The
    serving steps always pass it. Without ``prev`` the guard counts
    slots strictly *above* the limit (the clamp visibly changed them):
    an idle saturated slot (sitting exactly at the limit) is never
    re-counted, but one that keeps receiving traffic rises above the
    limit again each sweep and counts again — a per-sweep clamp-event
    count, not a once-only one. Pass ``prev`` when you need the latter.
    """
    lim = jnp.float32(limit)
    n_over = jnp.zeros((), jnp.int32)
    upd = {}
    for f in COUNT_FIELDS:
        r = getattr(state, f)
        if prev is not None:
            newly = (r >= lim) & (getattr(prev, f) < lim)
        else:
            newly = r > lim
        n_over = n_over + jnp.sum(newly.astype(jnp.int32))
        upd[f] = jnp.minimum(r, lim)
    return dataclasses.replace(state, **upd), n_over


# approx-LRU defaults: 2-bit age counters (pForest's choice) ranked by a
# 2-bit activity class — 16 score levels total
LRU_AGE_BITS = 2
LRU_ACT_BITS = 2

EVICT_POLICIES = ("timeout", "approx_lru")


def approx_lru_sweep(state: FlowTableState, w: "PacketWindow",
                     evict_age: float, *, occupancy: float = 0.75,
                     age_bits: int = LRU_AGE_BITS,
                     act_bits: int = LRU_ACT_BITS,
                     use_pallas=None) -> tuple:
    """pForest-style approx-LRU eviction: multi-bit age counters ranked by
    activity, swept only under occupancy pressure.

    The timeout sweep (``age_out``) evicts on idle time alone — under a
    DDoS flood of single-use flows it either churns the whole table (age
    too short) or lets dead flows squat until live ones cannot be
    admitted (age too long). This sweep instead ranks every occupied
    bucket by a small composite score and evicts only when (and only as
    much as) the table is under pressure:

      age class   = idle time quantized into ``2**age_bits`` levels so a
                    flow idle >= ``evict_age`` sits in the top class —
                    the multi-bit age counter of pForest's approx-LRU;
      activity    = ``log2(pkt_count)`` clipped to ``2**act_bits``
                    classes — bigger flows evict later within an age
                    class (flow-size ranking);
      score       = ``age_class * 2**act_bits + (2**act_bits - 1 -
                    act_class)``: oldest-then-smallest first.

    Nothing is evicted while occupancy (fraction of buckets with any
    packets) is at or below ``occupancy``. Above it, the smallest score
    threshold whose classes cover the excess is chosen from a score
    histogram and *every* bucket at or above it is recycled — class
    granularity is the "approx" in approx-LRU (the sweep may overshoot
    the high-water mark by up to one class). Flows seen in the current
    window are never evicted (same clamp discipline as ``evict_cutoff``),
    and an all-invalid (dead pad) window sweeps nothing. The reset rides
    the same masked-scatter ``kernels.ops.evict_fill`` as the timeout
    sweep. Returns (state, n_evicted i32).
    """
    n = state.n_buckets
    n_scores = 1 << (age_bits + act_bits)
    top_age = jnp.float32((1 << age_bits) - 1)
    top_act = jnp.float32((1 << act_bits) - 1)
    now = jnp.max(jnp.where(w.valid, w.ts, -jnp.inf))
    w_min = jnp.min(jnp.where(w.valid, w.ts, jnp.inf))
    occupied = state.pkt_count > 0
    n_occ = jnp.sum(occupied.astype(jnp.int32))
    high = jnp.int32(int(occupancy * n))
    pressure = jnp.any(w.valid) & (n_occ > high)
    # age/activity classes in float (inf-safe), cast after the clip
    period = jnp.float32(evict_age) / top_age
    idle = jnp.maximum(now - state.t_max, 0.0)
    age_cls = jnp.clip(jnp.floor(idle / period), 0.0, top_age)
    act_cls = jnp.clip(jnp.floor(jnp.log2(state.pkt_count + 1.0)),
                       0.0, top_act)
    score = (age_cls * (top_act + 1.0)
             + (top_act - act_cls)).astype(jnp.int32)
    protected = state.t_max >= w_min          # seen this window: survives
    eligible = occupied & ~protected
    score = jnp.where(eligible, score, -1)
    # smallest threshold whose classes cover the occupancy excess
    n_target = n_occ - high
    s = jnp.arange(n_scores, dtype=jnp.int32)
    counts = jnp.sum((score[None, :] == s[:, None]).astype(jnp.int32),
                     axis=1)
    cum = jnp.cumsum(counts[::-1])[::-1]      # cum[k] = #(score >= k)
    ok = cum >= n_target
    thr = jnp.where(jnp.any(ok), jnp.max(jnp.where(ok, s, -1)),
                    jnp.int32(0))
    evict = eligible & (score >= thr) & pressure
    regs = jnp.stack([getattr(state, f) for f in REGISTER_FIELDS])
    fills = jnp.asarray(EVICT_FILLS, jnp.float32)
    out = evict_fill(regs, evict, fills, use_pallas=use_pallas)
    new = FlowTableState(**{f: out[i]
                            for i, f in enumerate(REGISTER_FIELDS)})
    return new, jnp.sum(evict.astype(jnp.int32))


def evict_cutoff(ts, valid, evict_age: float):
    """Aging cutoff for one window: ``min(now - evict_age, window_min)``.

    Strictly no later than every timestamp in the window, so a flow seen
    in this window always survives it by construction — the single
    definition the reference sweep (``lifecycle_sweep``) and the chunked
    scan (``chunk_update_readout``) share; the bit-identity contract
    between the paths depends on the cutoff never diverging.
    """
    now = jnp.max(jnp.where(valid, ts, -jnp.inf))
    w_min = jnp.min(jnp.where(valid, ts, jnp.inf))
    return jnp.minimum(now - jnp.float32(evict_age), w_min)


def lifecycle_sweep(state: FlowTableState, w: "PacketWindow",
                    evict_age: Optional[float], saturate: bool,
                    prev: Optional[FlowTableState] = None, *,
                    evict_policy: str = "timeout",
                    lru_occupancy: float = 0.75) -> tuple:
    """Aging sweep + overflow guard for one served window.

    The single definition shared by the single-device and sharded serving
    steps — the sharded-vs-single-device bit-identity contract depends on
    the cutoff semantics never diverging between them. With the default
    ``evict_policy="timeout"`` the eviction cutoff is ``min(now -
    evict_age, window_min_ts)``: strictly no later than every timestamp
    in this window, so a flow seen in this window always survives it by
    construction, even when the window's time span exceeds ``evict_age``.
    ``evict_policy="approx_lru"`` substitutes the pressure-triggered
    pForest-style sweep (see ``approx_lru_sweep``; ``lru_occupancy`` is
    its high-water fraction) — same survive-this-window clamp, but
    eviction ranks age *and* activity and fires only above the occupancy
    mark. ``prev`` (the register file before this window's update) lets
    the overflow guard count only *newly* saturated slots — see
    ``saturate_counts``. Returns (state, n_evicted, n_overflow) — both
    counters zero when the corresponding feature is off.
    """
    n_ev = jnp.zeros((), jnp.int32)
    n_ov = jnp.zeros((), jnp.int32)
    if evict_policy not in EVICT_POLICIES:
        raise ValueError(f"evict_policy must be one of {EVICT_POLICIES}, "
                         f"got {evict_policy!r}")
    if evict_age is not None:
        if evict_policy == "approx_lru":
            state, n_ev = approx_lru_sweep(state, w, evict_age,
                                           occupancy=lru_occupancy)
        else:
            state, n_ev = age_out(state,
                                  evict_cutoff(w.ts, w.valid, evict_age))
    if saturate:
        state, n_ov = saturate_counts(state, prev=prev)
    return state, n_ev, n_ov


def flow_table_readout(state: FlowTableState,
                       bucket: Optional[jax.Array] = None) -> jax.Array:
    """Feature table from the registers — same columns as flow_features.

    bucket=None reads out every bucket -> (n_buckets, 8). Passing bucket
    ids gathers the 8 register vectors *first* and derives features on
    the gathered rows -> (len(bucket), 8): bit-identical (the derivation
    is elementwise) but ~n_buckets/len(bucket) less work — the serving
    step uses this to read out only the window's touched flows.
    """
    regs = (state.pkt_count, state.byte_count, state.t_min, state.t_max,
            state.fwd_pkts, state.rev_pkts, state.fwd_bytes,
            state.rev_bytes)
    if bucket is not None:
        regs = tuple(r[bucket] for r in regs)
    return table_from_registers(*regs)


def window_update_readout(state: FlowTableState, w: PacketWindow, *,
                          evict_age: Optional[float] = None,
                          saturate: bool = True,
                          evict_policy: str = "timeout",
                          lru_occupancy: float = 0.75,
                          use_pallas: Optional[bool] = None,
                          interpret: Optional[bool] = None) -> tuple:
    """Fold one window and read out its touched-flow feature rows.

    The serving steps' register half: update → aging sweep → overflow
    guard → touched-row readout, returning ``(state, x (W, 8), n_evicted,
    n_overflow)``. With ``use_pallas`` (default: auto, TPU only) the
    scatter-update, the 2^24 clamp and the touched-row gather run as ONE
    fused VMEM pass (``kernels.stream_update``) instead of scattering to
    HBM and gathering back; the XLA composition is the bit-equality
    oracle. The fusion is exact because

      * eviction cannot touch this window's rows (``evict_cutoff`` is
        clamped to the window minimum, and the approx-LRU sweep protects
        flows seen this window, so a flow seen here never evicts here) —
        sweeping *after* the gather reads the same bits;
      * clamping commutes with eviction (fills are in-envelope) and
        ``saturate_counts`` on an already-clamped file is a bitwise no-op
        that still counts newly saturated slots against ``prev``.
    """
    use_pallas = resolve_use_pallas(use_pallas)
    prev = state
    if not use_pallas:
        state = update_flow_table(state, w)
        state, n_ev, n_ov = lifecycle_sweep(state, w, evict_age, saturate,
                                            prev=prev,
                                            evict_policy=evict_policy,
                                            lru_occupancy=lru_occupancy)
        return state, flow_table_readout(state, w.bucket), n_ev, n_ov
    from repro.kernels.ops import stream_update
    regs = jnp.stack([getattr(state, f) for f in REGISTER_FIELDS])
    new_regs, rows = stream_update(
        regs, w.bucket, w.ts, w.length, w.is_fwd, w.valid,
        limit=OVERFLOW_LIMIT if saturate else None, interpret=interpret)
    state = FlowTableState(**{f: new_regs[i]
                              for i, f in enumerate(REGISTER_FIELDS)})
    # the shared sweep: the aging cutoff cannot touch this window's rows
    # and the clamp already landed in-kernel (saturate_counts is then a
    # bitwise no-op that still counts newly saturated slots vs ``prev``)
    state, n_ev, n_ov = lifecycle_sweep(state, w, evict_age, saturate,
                                        prev=prev,
                                        evict_policy=evict_policy,
                                        lru_occupancy=lru_occupancy)
    x = table_from_registers(*(rows[i] for i in range(len(REGISTER_FIELDS))))
    return state, x, n_ev, n_ov


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PacketChunk:
    """K windows stacked into one (K, W) device transfer.

    The chunked serving path (`serving.stream_serving`) runs the whole
    chunk as a single jitted ``lax.scan`` megastep, so the per-window
    Python→device dispatch cost is paid once per K windows. Leading-axis
    slices are exactly the ``PacketWindow``s the per-window path would
    have seen (the bit-equality oracle depends on this); a ragged final
    chunk is padded with *dead* windows — every lane invalid — which fold
    nothing into the registers, dispatch nothing, and report -1
    predictions on every lane.
    """
    bucket: jax.Array    # (K, W) int32 flow-hash bucket ids
    ts: jax.Array        # (K, W) f32 rebased seconds
    length: jax.Array    # (K, W) f32 packet bytes
    is_fwd: jax.Array    # (K, W) f32 1.0 = forward
    valid: jax.Array     # (K, W) bool (all-False row = dead pad window)

    @property
    def n_windows(self) -> int:
        return self.bucket.shape[0]

    @property
    def window(self) -> int:
        return self.bucket.shape[1]


def trace_columns(trace, n_buckets: int, *, t0: Optional[float] = None,
                  bucket=None) -> tuple:
    """Host-side per-packet columns shared by every window/chunk iterator
    AND the open-ended ingest ring (``netsim.ingest``). -> (cols, t0_used).

    Rebasing stays in float64 on host (see module docstring) and the
    bucket hash is elementwise (order-free), so every consumer — batch,
    per-window, chunked, or ring-buffered — presents bit-identical lanes
    to the jitted steps. t0=None latches the batch's minimum timestamp;
    the returned t0_used lets an open-ended caller latch it once on the
    first batch and rebase every later batch against the same epoch.
    """
    ts64 = np.asarray(trace.ts, np.float64)
    if t0 is None:
        t0 = float(ts64.min()) if ts64.size else 0.0
    if bucket is None:
        bucket = fnv1a_hash(
            trace.src_ip, trace.dst_ip, trace.sport, trace.dport,
            trace.proto, n_buckets=n_buckets)
    return dict(bucket=np.asarray(bucket, np.int32),
                ts=rebase_ts_np(ts64, t0),
                length=np.asarray(trace.length, np.float32),
                is_fwd=(np.asarray(trace.direction) == 0)
                .astype(np.float32)), t0


def _trace_columns(trace, n_buckets: int, t0: Optional[float], bucket):
    cols, _ = trace_columns(trace, n_buckets, t0=t0, bucket=bucket)
    return cols


def _pad_columns(cols: dict, n: int, total: int) -> dict:
    """Pad each (n,) column to ``total`` lanes replicating the last packet
    — the same in-distribution discipline as ``kernels.ops.pad_window``,
    applied once to the whole trace instead of per window."""
    if total == n:
        return cols
    return {k: np.concatenate([v, np.repeat(v[n - 1:n], total - n, axis=0)])
            for k, v in cols.items()}


def pack_chunk_columns(cols: dict, n: int, window: int, rows: int) -> tuple:
    """Pack ``n`` packets of host columns into ``rows`` windows of
    ``window`` lanes. -> (full_cols, valid) as flat (rows*window,) arrays.

    The single padding discipline shared by ``iter_chunks`` and the
    ingest ring's deadline/drain cuts (``netsim.ingest``): the ragged
    final *live* window replicate-pads the last packet (valid=False on
    the pad lanes), and any windows beyond the live ones are *dead* —
    all-zero columns, every lane invalid — so they fold nothing into the
    registers, dispatch nothing, and report -1 on every lane. Both
    callers produce bitwise-identical chunks because this is the only
    place the layout is defined.
    """
    n_win = -(-n // window) if n else 0
    if n_win > rows:
        raise ValueError(f"{n} packets need {n_win} windows of {window} "
                         f"lanes, only {rows} rows available")
    live = _pad_columns(cols, n, n_win * window)
    full = {k: np.zeros((rows * window,), v.dtype) for k, v in live.items()}
    for k, v in live.items():
        full[k][:n_win * window] = v
    valid = np.zeros((rows * window,), bool)
    valid[:n_win * window] = np.arange(n_win * window) < n
    return full, valid


def chunk_update_readout(state: FlowTableState, chunk: PacketChunk, *,
                         evict_age: Optional[float] = None,
                         saturate: bool = True,
                         evict_policy: str = "timeout",
                         lru_occupancy: float = 0.75,
                         use_pallas: Optional[bool] = None) -> tuple:
    """Whole-chunk sequential register half: fold K windows, emit rows.

    The chunked megastep's core — fold each of the chunk's K windows into
    the register file in order and stack the (W, 8) touched-row readouts
    as ``xs (K, W, 8)``; everything row-wise (classify, dispatch) runs on
    the stacked rows *after* this returns. Returns
    ``(state, xs, n_evicted, n_overflow)``, bit-identical to K
    ``window_update_readout`` steps.

    The XLA realization keeps the lax.scan body to the irreducibly
    sequential five memory ops — scatter-add the counts, scatter-min the
    2^24 clamp, scatter-min/max the timestamps, gather the touched rows —
    by moving everything window-local out of the loop: per-lane
    contribution vectors and identity-pinned timestamps are precomputed
    for the whole chunk (vectorized scan inputs), the six count
    registers ride ONE packed (N, 6) array and the two timestamp
    registers one (N, 2) array (t_min and *negated* t_max share a single
    scatter-min), and the feature derivation runs once over the stacked
    (K*W, 8) raw rows after the scan. Clamping only touched rows equals
    the per-window full-file clamp because the guard's invariant (every
    count <= 2^24 after every window, from init 0) makes it a no-op
    elsewhere. On TPU (``use_pallas``) the scan body is the fused Pallas
    scatter/readout kernel instead — the packing would only
    re-materialize what the kernel already holds in VMEM.

    Overflow telemetry is counted once per chunk from the entry/exit
    register files — exact, because clamped counts are monotone so a
    slot crosses the envelope at most once per chunk — except when
    eviction is also on (an evicted slot could re-cross), where a
    carried below-envelope mask restores exact per-window counting.
    """
    use_pallas = resolve_use_pallas(use_pallas)
    # the packed fast path below inlines *timeout* eviction into the scan
    # body; the approx-LRU sweep (histogram + threshold per window) runs
    # through the generic per-window body instead — same shape as the
    # Pallas branch, still one jitted scan megastep
    generic = use_pallas or (evict_age is not None
                             and evict_policy != "timeout")
    if generic:
        def body(state, cw):
            w = PacketWindow(bucket=cw.bucket, ts=cw.ts, length=cw.length,
                             is_fwd=cw.is_fwd, valid=cw.valid)
            state, x, n_ev, n_ov = window_update_readout(
                state, w, evict_age=evict_age, saturate=saturate,
                evict_policy=evict_policy, lru_occupancy=lru_occupancy,
                use_pallas=use_pallas)
            return state, (x, n_ev, n_ov)
        state, (xs, n_evs, n_ovs) = jax.lax.scan(body, state, chunk)
        return state, xs, jnp.sum(n_evs), jnp.sum(n_ovs)

    lim = jnp.float32(OVERFLOW_LIMIT)
    inf = jnp.float32(jnp.inf)
    k, w_lanes = chunk.bucket.shape
    # whole-chunk precompute: masked contribution vectors and pinned
    # timestamps enter the scan as vectorized inputs, not body ops
    wt = chunk.valid.astype(jnp.float32)
    ln, fwd = chunk.length, chunk.is_fwd
    vals = jnp.stack([wt, ln * wt, fwd * wt, (1.0 - fwd) * wt,
                      ln * fwd * wt, ln * (1.0 - fwd) * wt], axis=2)
    # t_min and -t_max share one packed scatter-min / gather
    tpin = jnp.stack([jnp.where(chunk.valid, chunk.ts, inf),
                      -jnp.where(chunk.valid, chunk.ts, -inf)], axis=2)
    lim_rows = jnp.full((w_lanes, 6), lim)
    counts0 = jnp.stack([getattr(state, f) for f in COUNT_FIELDS], axis=1)
    tmm0 = jnp.stack([state.t_min, -state.t_max], axis=1)
    # exact per-window overflow counting is only needed when eviction can
    # reset a saturated slot mid-chunk (see docstring)
    track_below = saturate and evict_age is not None
    carry = (counts0, tmm0,
             counts0 < lim if track_below else jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

    def body(carry, xs_in):
        counts, tmm, below, n_ev, n_ov = carry
        b, v, tp, valid, ts = xs_in
        counts = counts.at[b].add(v)
        if saturate:                       # clamp touched rows in place
            counts = counts.at[b].min(lim_rows)
        tmm = tmm.at[b].min(tp)
        if evict_age is not None:
            cutoff = evict_cutoff(ts, valid, evict_age)
            evict = (counts[:, 0] > 0) & (-tmm[:, 1] < cutoff)
            n_ev = n_ev + jnp.sum(evict.astype(jnp.int32))
            counts = jnp.where(evict[:, None], 0.0, counts)
            tmm = jnp.where(evict[:, None], inf, tmm)
        if track_below:
            n_ov = n_ov + jnp.sum(((counts >= lim) & below)
                                  .astype(jnp.int32))
            below = counts < lim
        x = jnp.concatenate([counts[b], tmm[b]], axis=1)   # raw (W, 8)
        return (counts, tmm, below, n_ev, n_ov), x

    (counts, tmm, _, n_ev, n_ov), raw = jax.lax.scan(
        body, carry, (chunk.bucket, vals, tpin, chunk.valid, chunk.ts))
    if saturate and not track_below:       # once per chunk: exact (monotone)
        n_ov = jnp.sum(((counts >= lim) & (counts0 < lim))
                       .astype(jnp.int32))
    raw = raw.reshape(k * w_lanes, 8)      # derive features post-scan
    xs = table_from_registers(raw[:, 0], raw[:, 1], raw[:, 6], -raw[:, 7],
                              raw[:, 2], raw[:, 3], raw[:, 4], raw[:, 5]
                              ).reshape(k, w_lanes, FLOW_FEATURES)
    state = FlowTableState(
        t_min=tmm[:, 0], t_max=-tmm[:, 1],
        **{f: counts[:, i] for i, f in enumerate(COUNT_FIELDS)})
    return state, xs, n_ev, n_ov


def iter_windows(trace, window: int, n_buckets: int, *,
                 t0: Optional[float] = None, bucket=None,
                 pad: bool = True, device: bool = True
                 ) -> Iterator[PacketWindow]:
    """Chunk a PacketTrace into fixed-size PacketWindows.

    Hashing is elementwise (order-free), so per-window bucket ids equal
    the batch path's; pass ``bucket`` to reuse an already-computed full-
    trace hash. t0 is the stream epoch every window rebases against; it
    defaults to the trace's *minimum* timestamp — the batch path's epoch,
    so reordered packets rebase identically to ``flow_features`` (latching
    the first packet instead shifted every f32 rounding when the stream
    opened out of order). Callers that cannot pre-scan an open-ended
    stream pass an explicit provisional t0; the sharded tier min-merges
    the true epoch as a register and corrects at readout. pad=True
    tile-pads the final ragged window to ``window`` lanes (valid=False)
    so every window presents one static shape to jitted consumers.

    device=True (default) transfers each column ONCE and slices windows
    on device — the per-window cost drops from four host→device copies
    to one row slice of a resident (n_windows, W) array. device=False
    keeps the host-slicing path for open-ended streams that are fed
    window by window and can never be materialized whole; pad=False
    implies it (a ragged window has no static device shape).
    """
    cols = _trace_columns(trace, n_buckets, t0, bucket)
    n = len(cols["ts"])
    if not pad:
        device = False
    if device:
        if not n:
            return
        n_win = -(-n // window)
        cols = _pad_columns(cols, n, n_win * window)
        dev = {k: jnp.asarray(v.reshape(n_win, window))
               for k, v in cols.items()}
        valid = jnp.asarray(
            (np.arange(n_win * window) < n).reshape(n_win, window))
        for k in range(n_win):
            yield PacketWindow(valid=valid[k],
                               **{f: dev[f][k] for f in dev})
        return
    for s in range(0, n, window):
        sl = slice(s, s + window)
        w_cols = {k: jnp.asarray(v[sl]) for k, v in cols.items()}
        if pad:
            w_cols, valid, _ = pad_window(w_cols, window)
        else:
            valid = jnp.ones(w_cols["bucket"].shape[0], bool)
        yield PacketWindow(valid=valid, **w_cols)


def iter_chunks(trace, window: int, chunk_windows: int, n_buckets: int, *,
                t0: Optional[float] = None, bucket=None
                ) -> Iterator[PacketChunk]:
    """Stack the trace's windows K at a time into (K, W) PacketChunks.

    One device transfer per column for the whole trace, one row-range
    slice per chunk — the host never touches per-window data again. Row
    k of a chunk is bit-identical to the k-th ``iter_windows`` window
    (same padding discipline, same rebase); the final chunk is padded to
    K rows with dead windows (valid all-False) so every chunk presents
    one static (K, W) shape to the jitted scan megastep.
    """
    cols = _trace_columns(trace, n_buckets, t0, bucket)
    n = len(cols["ts"])
    if not n:
        return
    n_win = -(-n // window)
    n_chunks = -(-n_win // chunk_windows)
    rows = n_chunks * chunk_windows
    # shared packing discipline (ragged live window replicate-pads, dead
    # pad windows are all-zero/invalid) — see pack_chunk_columns
    full, valid = pack_chunk_columns(cols, n, window, rows)
    dev = {k: jnp.asarray(v.reshape(rows, window)) for k, v in full.items()}
    valid = jnp.asarray(valid.reshape(rows, window))
    for c in range(n_chunks):
        sl = slice(c * chunk_windows, (c + 1) * chunk_windows)
        yield PacketChunk(valid=valid[sl], **{f: dev[f][sl] for f in dev})


# module-level so repeated stream_flow_features calls share the jit cache
_update_flow_table_jit = jax.jit(update_flow_table, donate_argnums=0)


def stream_flow_features(trace, n_buckets=4096, window=1024, *,
                         t0: Optional[float] = None):
    """One-shot convenience: stream the whole trace window by window.

    Returns (bucket_ids (P,), flow_table (n_buckets, 8)) — bit-consistent
    with ``features.flow_features`` on the same trace (the equivalence
    oracle used by tests and benchmarks/stream_bench.py). t0 overrides
    the stream epoch (default: trace minimum, matching the batch path
    even when packets arrive out of order).
    """
    b = fnv1a_hash(trace.src_ip, trace.dst_ip, trace.sport, trace.dport,
                   trace.proto, n_buckets=n_buckets)
    state = init_flow_table(n_buckets)
    for w in iter_windows(trace, window, n_buckets, bucket=b, t0=t0):
        state = _update_flow_table_jit(state, w)
    return b, flow_table_readout(state)
