"""Sharded streaming hybrid serving: the flow table scaled out over a mesh.

``ShardedStreamingServer`` is the ``StreamingHybridServer`` with its
register file partitioned across a 2D ('shard', 'data') device mesh
(``netsim.shard_stream`` / DESIGN.md §16): each ``step(window)`` is still
ONE jitted, state-donating dispatch, but the register update runs under
``shard_map`` — every shard folds only the buckets it owns
(bucket % n_shards), so the table capacity and the scatter bandwidth
scale with the mesh while the step keeps the parent's exact shape:

  shard_map:  per-shard register update (+ aging sweep + overflow guard)
              -> owner-masked touched-flow readout
              -> PARTITIONED classify: reduce-scatter the owner-masked
                 rows into complete ceil(K*W/D)-row lane slabs, fused
                 classify the slab only, all-gather the compact
                 (pred, conf) vectors back to full width
              -> capacity-bounded dispatch -> psum-merge backend buffer
  jit level:  backend -> combine -> StreamStats accumulation (the same
              ``accumulate_stream_stats`` the single-device tier uses)

The 'shard' axis partitions storage (flow-table buckets); the 'data'
axis adds pure batch parallelism over the classify lanes and the backend
slices (registers replicate along it). Per-device classify work is
~K*W/(D_shard*D_data) rows instead of K*W — the replicated-classify
layout this replaced survives as ``partition_classify=False``, the
``merge_overhead`` baseline the shard bench reports speedups against.

Cross-device traffic is only the small merges: the lane-slab
reduce-scatter/all-gathers, the (capacity, F) backend buffer psum, and
the i32 telemetry counters — never the register file itself (per-bucket
independence is what makes the flow table shardable at all).

Contract (tests + benchmarks/shard_stream_bench.py): with eviction
disabled, the sharded server is bit-identical to the single-device
``StreamingHybridServer`` on in-order traces — same predictions, same
telemetry, same ``flow_table()`` readout — at every mesh shape. The
reduce-scatter of owner-masked rows sums exactly one real row plus
zeros per lane, so each device's slab holds the owner's rows bitwise,
and classify is row-independent — partitioning moves work, not values.

Out-of-order arrivals (including a reordered first window) are tolerated
because every register is an associative reduction and every feature an
epoch-invariant difference; the min-merged ``epoch`` register replaces
the host-side latch as the record of the stream's true time origin
(``.epoch`` telemetry). The same donation discipline as the parent
applies — state and stats carries are consumed every step.

Cross-window batching is shard-aware (DESIGN.md §7): with
``flush_every=k`` the per-window psum of the dispatch buffer disappears
entirely — each shard accumulates the partial rows it owns in its slice
of the (n_shards, k*capacity, F) deferral buffer, and a flush
reduce-scatters complete rows so every device's backend serves only
k*capacity/(D_shard*D_data) of them. Backend capacity scales with the
whole mesh; the flush_every=1 default keeps the per-window
replicated-buffer path bit for bit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.artifact import TableArtifact
from repro.core.hybrid import (DeferredDispatch, backpatch_pending,
                               chunk_dispatch, dispatch, init_deferred)
from repro.distributed.sharding import as_flow_mesh, flow_shard_mesh
from repro.kernels.ops import classify_batch_rows, fused_classify
from repro.kernels.tuning import (TileConfig, resolve_use_pallas,
                                  shard_tiles)
from repro.netsim.shard_stream import (ShardedFlowTable, gather_lane_values,
                                       init_sharded_table, lane_slab_rows,
                                       n_local_buckets, scatter_lane_slab,
                                       shard_window_update,
                                       sharded_flow_table, stream_epoch)
from repro.netsim.stream import FLOW_FEATURES, PacketChunk, PacketWindow
from repro.serving.faults import FaultPolicy
from repro.serving.stream_serving import (StreamingHybridServer,
                                          accumulate_chunk_stats,
                                          accumulate_stream_stats,
                                          chunk_classify_tail,
                                          defer_tail, fold_flush_stats)


class ShardedStreamingServer(StreamingHybridServer):
    """StreamingHybridServer over a bucket-sharded register file.

    mesh (or n_shards / n_data) picks the 2D ('shard', 'data') mesh —
    default every local device on 'shard'; a legacy 1D ('shard',) mesh is
    normalized to a size-1 'data' axis. n_buckets is the *global* table
    size and must divide evenly over the shards. All parent knobs
    (threshold, capacity, evict_age, saturate, tiles, fuse) keep their
    meaning; ``step``/``serve_trace``/``reset`` are inherited — only the
    jitted closures and the state layout differ.
    ``partition_classify=False`` restores the pre-partitioning layout
    (every device classifies all lanes, owner-masked psum merge) — the
    ``merge_overhead`` baseline of the shard bench.
    """

    # Hot-path auditor contracts (repro.analysis.hotpath). The census
    # pins DESIGN.md §6/§8/§16 exactly: each audited step pays ONE
    # rank-2 lane-slab reduce-scatter (jax lowers psum_scatter to the
    # reduce_scatter primitive), TWO all-gathers (the compact pred and
    # conf slabs coming back), and three psums — the dispatch/deferral
    # buffer (the single rank>=2 "readout" psum) plus the two scalar
    # evict/overflow counts. The chunk megastep amortizes all of it to
    # once per K windows. Any extra collective that sneaks into these
    # jaxprs is a regression the auditor rejects; the census is
    # mesh-shape-invariant, so it holds on the 1-device audit mesh and
    # the (2, 2) CI mesh alike.
    AUDIT_CONTRACTS = (
        {"attr": "_stream_step", "donate": (1, 2), "probe": "window",
         "collectives": {"psum": 3, "reduce_scatter": 1, "all_gather": 2},
         "readout_psums": 1, "readout_scatters": 1},
        {"attr": "_stream_switch", "donate": (1,), "probe": "window",
         "collectives": {"psum": 3, "reduce_scatter": 1, "all_gather": 2},
         "readout_psums": 1, "readout_scatters": 1},
        {"attr": "_chunk_step", "donate": (1, 2), "probe": "chunk",
         "collectives": {"psum": 3, "reduce_scatter": 1, "all_gather": 2},
         "readout_psums": 1, "readout_scatters": 1},
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
                 mesh: Optional[Mesh] = None, n_shards: Optional[int] = None,
                 n_data: Optional[int] = None,
                 partition_classify: bool = True,
                 use_pallas: Optional[bool] = None, autotune: bool = False,
                 tiles: Optional[TileConfig] = None,
                 fuse: Optional[bool] = None, obs=None):
        use_pallas = resolve_use_pallas(use_pallas)
        # mesh before super().__init__: the parent allocates the register
        # file through the _make_state hook, which needs it
        if mesh is not None:
            self.mesh = as_flow_mesh(mesh)
        else:
            self.mesh = flow_shard_mesh(n_shards, n_data or 1)
        n_sh = self.n_shards = self.mesh.shape["shard"]
        n_dt = self.n_data = self.mesh.shape["data"]
        n_dev = self.n_devices = n_sh * n_dt
        self.partition_classify = bool(partition_classify)
        n_local_buckets(n_buckets, n_sh)          # validate divisibility
        if flush_every > 1 and (flush_every * capacity) % n_dev:
            # flush_every == 1 never builds the deferral buffer, so the
            # per-device slice constraint does not apply there
            raise ValueError(
                f"flush_every*capacity={flush_every * capacity} must divide "
                f"evenly over {n_dev} devices (each device's backend serves "
                f"one slice of the deferral buffer per flush)")
        # "auto" resolves inside the parent init (through the
        # _auto_chunk_filter override below, which enforces this same
        # divisibility on every candidate), so only explicit ints are
        # checked here
        if (isinstance(chunk_windows, int)
                and (chunk_windows * capacity) % n_dev):
            raise ValueError(
                f"chunk_windows*capacity={chunk_windows * capacity} must "
                f"divide evenly over {n_dev} devices (each device's backend "
                f"serves one slice of the chunk's deferral buffer)")
        super().__init__(artifact, backend_fn, n_buckets=n_buckets,
                         window=window, threshold=threshold,
                         capacity=capacity, flush_every=flush_every,
                         chunk_windows=chunk_windows,
                         flush_occupancy=flush_occupancy,
                         flush_deadline=flush_deadline,
                         evict_age=evict_age,
                         saturate=saturate, evict_policy=evict_policy,
                         lru_occupancy=lru_occupancy,
                         fault_policy=fault_policy, use_pallas=use_pallas,
                         autotune=autotune, tiles=tiles, fuse=fuse, obs=obs)

        def _slab_classify(art, x):
            """Partitioned fused classify (DESIGN.md §16): reduce-scatter
            the owner-masked (N, F) rows into complete per-device lane
            slabs, classify ONLY the ceil(N/D)-row slab, all-gather the
            compact (pred, conf) vectors back to the replicated full
            width. Bit-identical to classifying the full width because
            each complete row equals the owner's row exactly (one real
            value plus zeros) and classify is row-independent. tile_n is
            clamped to the slab so the kernel grid never pads the
            partitioned batch back up toward N."""
            n_lanes = x.shape[0]
            t = lane_slab_rows(n_lanes, n_sh, n_dt)
            sl = scatter_lane_slab(x, n_sh, n_dt)
            pred, conf = fused_classify(art, sl, use_pallas=use_pallas,
                                        tiles=shard_tiles(self.tiles, t))
            return (gather_lane_values(pred.astype(jnp.int32), n_lanes),
                    gather_lane_values(conf, n_lanes))

        def _shard_body(regs, epoch, art, w: PacketWindow, threshold, *,
                        merge_buf):
            """Per-shard half of the step (runs under shard_map; regs
            leaves arrive as this shard's (1, n_local) block). merge_buf
            psums the dispatch buffer to a replicated (capacity, F) for
            the immediate backend; the deferred path skips that merge and
            keeps each shard's partial rows — they accumulate in the
            deferral buffer and are reduce-scattered once per flush."""
            sq = jax.tree.map(lambda a: a[0], regs)
            d = jax.lax.axis_index("shard")
            sq, e, own, x, n_ev, n_ov = shard_window_update(
                sq, w, n_sh, d, evict_age=evict_age, saturate=saturate,
                evict_policy=evict_policy, lru_occupancy=lru_occupancy)
            if self.partition_classify:
                sw_pred, conf = _slab_classify(art, x)
            else:
                # merge_overhead baseline: every device classifies all W
                # lanes; exact merges — exactly one shard contributes a
                # nonzero lane
                sw_pred, conf = fused_classify(art, x, use_pallas=use_pallas,
                                               tiles=self.tiles)
                sw_pred = jax.lax.psum(jnp.where(own, sw_pred, 0), "shard")
                conf = jax.lax.psum(jnp.where(own, conf, 0.0), "shard")
            fwd = (conf < threshold) & w.valid
            buf, idx, valid = dispatch(x, fwd, capacity)
            buf = jax.lax.psum(buf, "shard") if merge_buf else buf[None]
            counts = (jax.lax.psum(n_ev, "shard"),
                      jax.lax.psum(n_ov, "shard"))
            return (jax.tree.map(lambda a: a[None], sq),
                    jnp.minimum(epoch, e),
                    sw_pred, fwd, buf, idx, valid, conf, counts)

        # check_vma=False: jax's static replication checker cannot infer
        # replication through all_gather (the partitioned classify's
        # merge); the out_specs still pin the layout, and the bit-identity
        # oracles pin the values.
        state_specs = (P("shard", None), P("shard"), P(), P(), P())
        shard_half = jax.shard_map(
            functools.partial(_shard_body, merge_buf=True), mesh=self.mesh,
            in_specs=state_specs,
            out_specs=(P("shard", None), P("shard"),
                       P(), P(), P(), P(), P(), P(), P()),
            check_vma=False)
        defer_half = jax.shard_map(
            functools.partial(_shard_body, merge_buf=False), mesh=self.mesh,
            in_specs=state_specs,
            out_specs=(P("shard", None), P("shard"),
                       P(), P(), P("shard", None, None), P(), P(), P(), P()),
            check_vma=False)

        def _switch_half(art, state: ShardedFlowTable, w, threshold, *,
                         half=shard_half):
            (regs, epoch, sw_pred, fwd, buf, idx, valid, conf,
             counts) = half(state.regs, state.epoch, art, w, threshold)
            return (ShardedFlowTable(regs=regs, epoch=epoch),
                    sw_pred, fwd, buf, idx, valid, conf, counts)

        def stream_step(art, state, stats, w: PacketWindow, threshold):
            (state, sw_pred, fwd, buf, idx, valid, conf,
             counts) = _switch_half(art, state, w, threshold)
            be_pred = jnp.asarray(backend_fn(buf))
            stats, pred, frac, rows = accumulate_stream_stats(
                stats, w, sw_pred, be_pred, idx, valid, fwd, conf, *counts)
            return state, stats, pred, frac, rows

        self._stream_step = jax.jit(stream_step, donate_argnums=(1, 2))

        def stream_switch(art, state, w: PacketWindow, threshold):
            return _switch_half(art, state, w, threshold)

        self._stream_switch = jax.jit(stream_switch, donate_argnums=(1,))
        # the epilogue (accumulate_stream_stats) is inherited as-is

        # -- cross-window deferred dispatch (shard-aware) --------------------

        def defer_step(art, state, stats, dd, pending, w, threshold, pos):
            """Deferred-path window: the parent's shared tail, but the
            dispatch buffer stays per-shard partial ((n_shards, capacity,
            F), the rows each shard owns, zeros elsewhere) — no
            per-window psum."""
            (state, sw_pred, fwd, buf, idx, valid, conf,
             counts) = _switch_half(art, state, w, threshold,
                                    half=defer_half)
            stats, dd, pending, pred, frac, rows = defer_tail(
                stats, dd, pending, w, sw_pred, fwd, buf, idx, valid,
                conf, counts, pos)
            return state, stats, dd, pending, pred, frac, rows

        self._defer_step = jax.jit(defer_step, donate_argnums=(1, 2, 3, 4))

        def _flush_body(buf):
            """Per-device flush half: reduce-scatter the partial deferral
            buffers over 'shard' so each shard holds complete rows for
            its slice, then slice that block again by the 'data' index —
            every one of the D_shard*D_data devices' backends serves
            slots/D rows, and the ('shard', 'data')-concatenated out_spec
            reassembles the full (slots,) answer vector in slot order."""
            sl = jax.lax.psum_scatter(buf[0], "shard", scatter_dimension=0,
                                      tiled=True)
            per = sl.shape[0] // n_dt
            i = jax.lax.axis_index("data")
            sl = jax.lax.dynamic_slice_in_dim(sl, i * per, per)
            return jnp.asarray(backend_fn(sl)).astype(jnp.int32)

        flush_half = jax.shard_map(_flush_body, mesh=self.mesh,
                                   in_specs=(P("shard", None, None),),
                                   out_specs=P(("shard", "data")),
                                   check_vma=False)

        def flush_fused(stats, dd, pending):
            be_pred = flush_half(dd.buf)
            patched = backpatch_pending(pending, be_pred, dd)
            stats = fold_flush_stats(stats, dd)
            return (stats, jax.tree.map(jnp.zeros_like, dd), patched,
                    jnp.full_like(pending, -1))

        self._flush_fused = jax.jit(flush_fused, donate_argnums=(0, 1, 2))
        # _flush_patch (two-phase: host backend on summed partial rows,
        # jitted back-patch) is inherited — backpatch/fold are layout-
        # agnostic and _flush_rows_host sums the shard dim.

        # -- device-resident chunked streaming (shard_map over the scan
        # -- body: the sequential register half runs per shard) -------------

        def _chunk_register_scan(regs, epoch, chunk: PacketChunk):
            """Shared sequential core of both chunk bodies: carry this
            shard's register block through the K owner-masked
            scatter-update + readout steps, stacking owner-masked (W, 8)
            readout partials."""
            sq = jax.tree.map(lambda a: a[0], regs)
            d = jax.lax.axis_index("shard")

            def body(carry, cw: PacketChunk):
                sq, ep = carry
                w = PacketWindow(bucket=cw.bucket, ts=cw.ts,
                                 length=cw.length, is_fwd=cw.is_fwd,
                                 valid=cw.valid)
                sq, e, own, x, n_ev, n_ov = shard_window_update(
                    sq, w, n_sh, d, evict_age=evict_age, saturate=saturate,
                    evict_policy=evict_policy, lru_occupancy=lru_occupancy)
                return (sq, jnp.minimum(ep, e)), (x, n_ev, n_ov)

            return jax.lax.scan(body, (sq, epoch[0]), chunk)

        if self.partition_classify:

            def _chunk_part_body(regs, epoch, art, chunk: PacketChunk,
                                 threshold):
                """Per-shard chunk megastep core: the register scan, then
                the partitioned classify over the chunk's K*W lane rows
                (one ceil(K*W/D)-row slab per device) and the per-shard
                capacity-bounded dispatch — the deferred rows merge
                through ONE rank-2 psum, the chunk's single readout
                merge."""
                (sq, ep), (xs, n_evs, n_ovs) = _chunk_register_scan(
                    regs, epoch, chunk)
                k, w_lanes, nf = xs.shape
                sw_pred, conf = _slab_classify(art, xs.reshape(k * w_lanes,
                                                               nf))
                sw_pred = sw_pred.reshape(k, w_lanes)
                conf = conf.reshape(k, w_lanes)
                fwd = (conf < threshold) & chunk.valid
                dd = chunk_dispatch(xs, fwd, capacity)
                dd = dataclasses.replace(
                    dd, buf=jax.lax.psum(dd.buf, "shard"))
                n_ev = jax.lax.psum(jnp.sum(n_evs), "shard")
                n_ov = jax.lax.psum(jnp.sum(n_ovs), "shard")
                return (jax.tree.map(lambda a: a[None], sq), ep[None],
                        sw_pred, conf, fwd, dd, n_ev, n_ov)

            dd_specs = DeferredDispatch(buf=P(), lane=P(), window=P(),
                                        valid=P())
            chunk_part_half = jax.shard_map(
                _chunk_part_body, mesh=self.mesh,
                in_specs=(P("shard", None), P("shard"), P(), P(), P()),
                out_specs=(P("shard", None), P("shard"),
                           P(), P(), P(), dd_specs, P(), P()),
                check_vma=False)

            def chunk_switch(art, state, stats, chunk: PacketChunk,
                             threshold):
                """Sharded chunk megastep switch half: everything down to
                the dispatch runs inside ONE shard_map (classify included
                — that is the point), leaving only the layout-agnostic
                whole-chunk stats fold and the provisional prediction set
                at the jit level. Identical math to the single-device
                ``chunk_classify_tail``, which is the bit-identity
                contract."""
                (regs, epoch, sw_pred, conf, fwd, dd, n_ev,
                 n_ov) = chunk_part_half(state.regs, state.epoch, art,
                                         chunk, threshold)
                state = ShardedFlowTable(regs=regs, epoch=epoch)
                stats, frac, rows = accumulate_chunk_stats(
                    stats, chunk, fwd, dd, conf, n_ev, n_ov)
                pending = jnp.where(chunk.valid, sw_pred, -1)  # pad lanes
                return state, stats, dd, pending, frac, rows

        else:

            def _chunk_scan_body(regs, epoch, chunk: PacketChunk):
                """merge_overhead baseline chunk body: ONE psum over the
                stacked (K, W, 8) readout rows completes them; the
                parent's replicated ``chunk_classify_tail`` then
                classifies all K*W rows on every device."""
                (sq, ep), (xs, n_evs, n_ovs) = _chunk_register_scan(
                    regs, epoch, chunk)
                xs = jax.lax.psum(xs, "shard")  # owner partials -> complete
                n_ev = jax.lax.psum(jnp.sum(n_evs), "shard")
                n_ov = jax.lax.psum(jnp.sum(n_ovs), "shard")
                return (jax.tree.map(lambda a: a[None], sq), ep[None],
                        xs, n_ev, n_ov)

            chunk_scan_half = jax.shard_map(
                _chunk_scan_body, mesh=self.mesh,
                in_specs=(P("shard", None), P("shard"), P()),
                out_specs=(P("shard", None), P("shard"), P(), P(), P()),
                check_vma=False)

            def chunk_switch(art, state, stats, chunk: PacketChunk,
                             threshold):
                regs, epoch, xs, n_ev, n_ov = chunk_scan_half(
                    state.regs, state.epoch, chunk)
                state = ShardedFlowTable(regs=regs, epoch=epoch)
                stats, dd, pending, frac, rows = chunk_classify_tail(
                    art, stats, chunk, xs, n_ev, n_ov, threshold, capacity,
                    use_pallas=use_pallas, tiles=self.tiles)
                return state, stats, dd, pending, frac, rows

        self._chunk_switch = jax.jit(chunk_switch, donate_argnums=(1, 2))

        chunk_be_half = jax.shard_map(
            lambda bs: jnp.asarray(backend_fn(bs[0])).astype(jnp.int32),
            mesh=self.mesh, in_specs=(P(("shard", "data"), None, None),),
            out_specs=P(("shard", "data")), check_vma=False)

        def chunk_step(art, state, stats, chunk: PacketChunk, threshold):
            """Megastep with the mesh-wide backend: the chunk's deferred
            rows are complete (the readout psum already merged them), so
            each of the D_shard*D_data devices' backends serves one
            (K*capacity/D)-row slice and the concatenated answers
            back-patch the stacked predictions — still one device
            dispatch per chunk."""
            state, stats, dd, pending, frac, rows = chunk_switch(
                art, state, stats, chunk, threshold)
            slots = dd.buf.shape[0]
            be_pred = chunk_be_half(
                dd.buf.reshape(n_dev, slots // n_dev, FLOW_FEATURES))
            patched = backpatch_pending(pending, be_pred, dd)
            return state, stats, patched, frac, rows

        self._chunk_step = jax.jit(chunk_step, donate_argnums=(1, 2))
        # _chunk_patch (two-phase epilogue) is inherited — the chunk's
        # deferred rows are already complete, so the host path needs no
        # shard-dim sum either.

    # -- partitioned-classify telemetry -------------------------------------

    @property
    def classify_rows_per_device(self) -> int:
        """Rows each device's fused classify actually processes per
        megastep, kernel tile padding included (``classify_batch_rows``).

        Partitioned (the default): one ceil(K*W / (D_shard*D_data))-row
        lane slab per device. merge_overhead baseline
        (``partition_classify=False``): the full K*W lanes, replicated.
        The shard bench gates on the partitioned value being the padded
        ceiling — per-device classify work must shrink with the mesh.
        """
        lanes = (self.chunk_windows or 1) * self.window
        if not self.partition_classify:
            return classify_batch_rows(self.artifact, lanes,
                                       use_pallas=self.use_pallas,
                                       tiles=self.tiles)
        t = lane_slab_rows(lanes, self.n_shards, self.n_data)
        return classify_batch_rows(self.artifact, t,
                                   use_pallas=self.use_pallas,
                                   tiles=shard_tiles(self.tiles, t))

    # -- chunk-size autotune hooks ------------------------------------------

    def _auto_chunk_server(self, k: int, artifact, backend_fn, **kw):
        """Sweep throwaways share this server's mesh and classify layout
        so candidate timings include the real collectives."""
        return ShardedStreamingServer(
            artifact, backend_fn, chunk_windows=k, mesh=self.mesh,
            partition_classify=self.partition_classify, **kw)

    def _auto_chunk_filter(self, capacity: int):
        """Only Ks whose chunk deferral buffer divides over the mesh
        (the per-device backend-slice constraint validated in __init__)."""
        n_dev = self.n_devices
        return lambda k: (k * capacity) % n_dev == 0

    # -- streaming state ----------------------------------------------------

    def _make_state(self) -> ShardedFlowTable:
        """Mesh-placed sharded register file (parent init/reset hook)."""
        return init_sharded_table(self.n_buckets, mesh=self.mesh)

    def _make_deferred(self) -> DeferredDispatch:
        """Per-shard partial-row deferral buffer, placed on the mesh:
        the (n_shards, slots, F) accumulation buffer shards its leading
        dim over 'shard' (replicated along 'data'); the return addresses
        are replicated."""
        dd = init_deferred(self.flush_every, self.capacity, FLOW_FEATURES,
                           n_shards=self.n_shards)
        sh = lambda *spec: NamedSharding(self.mesh, P(*spec))
        return DeferredDispatch(
            buf=jax.device_put(dd.buf, sh("shard", None, None)),
            lane=jax.device_put(dd.lane, sh()),
            window=jax.device_put(dd.window, sh()),
            valid=jax.device_put(dd.valid, sh()))

    def flow_table(self) -> jax.Array:
        """(n_buckets, 8) canonical-bucket-order table, gathered across
        shards (a telemetry/test readout, not a hot path). Timestamps in
        the underlying registers stay in the provisional rebased frame —
        combine with ``.epoch`` for wall-clock flow times."""
        return sharded_flow_table(self._state)

    @property
    def epoch(self) -> float:
        """True observed stream start (min-merged register), in the
        provisional rebased frame; 0.0 for an in-order stream."""
        return float(stream_epoch(self._state))
