"""Sharded flow-table tier: the register file partitioned across a mesh.

A single device's register file bounds how many flows the streaming tier
can track; a production deployment shards the table across devices the
way a switch ASIC banks its SRAM. This module partitions the
``FlowTableState`` buckets over a 1D ('shard',) mesh by

    owner(bucket)  = bucket % n_shards
    local(bucket)  = bucket // n_shards

so global bucket ``b`` lives at row ``b // n_shards`` of shard
``b % n_shards`` — the interleaved layout keeps the FNV hash's bucket
distribution uniform per shard. Register leaves carry a leading shard
dim: ``(n_shards, n_local)``, sharded ``P('shard', None)``; the canonical
bucket order is recovered by ``leaf.T.reshape(-1)``.

The per-window step runs under ``shard_map``: every shard receives the
(replicated) window, masks it down to the packets it owns, and folds
them with the *same* ``update_flow_table`` segment-scatter the
single-device tier uses — per-bucket independence means zero cross-device
traffic for the update itself. Readout gathers each packet's row from
its owner shard; non-owner contributions are zeroed so the small psum
merges (predictions, confidences, the capacity-bounded backend buffer,
telemetry counters) are exact: one real value plus zeros. This keeps the
sharded step bit-identical to ``StreamingHybridServer`` on in-order
traces with eviction disabled (the contract tests and the shard bench
oracle assert).

Out-of-order tolerance: every register is an associative, order-free
reduction (sums, min, max), and every derived feature is epoch-invariant
(durations and IATs are timestamp *differences*), so reordered arrivals
— including a reordered first window — fold into the same table
regardless of which provisional ``t0`` the host rebased against. What a
host-side latch cannot provide is the stream's true time origin: that is
the min-merged ``ShardedFlowTable.epoch`` register, which accumulates
the minimum observed relative timestamp (0.0 on an in-order stream,
negative when the true start arrived after the provisional latch) — the
subsystem's source of truth for mapping register timestamps back to
wall clock and for aging decisions that outlive a single host.

Flow lifecycle folds into the same step: ``shard_window_update``
optionally runs the ``age_out`` eviction sweep (idle buckets recycled to
the init identities) and the ``saturate_counts`` overflow guard (clamp at
the 2^24 f32 integer-exactness envelope) per shard, per window.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import flow_shard_mesh, flow_table_sharding
from repro.netsim.features import fnv1a_hash, table_from_registers
from repro.netsim.stream import (REGISTER_FIELDS, FlowTableState,
                                 PacketWindow, flow_table_readout,
                                 iter_windows, lifecycle_sweep,
                                 update_flow_table)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedFlowTable:
    """Register file partitioned over the 'shard' mesh axis.

    regs leaves are (n_shards, n_local) — shard d's block at [d]; epoch
    is the (n_shards,) min-merged stream-epoch register (every shard sees
    every window, so all rows agree; the min over rows is the stream's
    true observed start in the provisional rebased frame, +inf before any
    packet).
    """
    regs: FlowTableState
    epoch: jax.Array

    @property
    def n_shards(self) -> int:
        return self.regs.pkt_count.shape[0]

    @property
    def n_buckets(self) -> int:
        return self.regs.pkt_count.shape[0] * self.regs.pkt_count.shape[1]


def n_local_buckets(n_buckets: int, n_shards: int) -> int:
    if n_buckets % n_shards:
        raise ValueError(f"n_buckets={n_buckets} must divide evenly over "
                         f"{n_shards} shards")
    return n_buckets // n_shards


def init_sharded_table(n_buckets: int, *, mesh: Optional[Mesh] = None,
                       n_shards: Optional[int] = None) -> ShardedFlowTable:
    """Fresh sharded register file, placed on ``mesh`` when given.

    Same init identities as ``init_flow_table`` (counts 0, t_min/t_max at
    the segment identities) so an untouched sharded bucket reads out
    bit-identically to an untouched single-device one.
    """
    if mesh is not None:
        n_shards = mesh.shape["shard"]
    n_local = n_local_buckets(n_buckets, n_shards)
    z = lambda: jnp.zeros((n_shards, n_local), jnp.float32)
    regs = FlowTableState(
        pkt_count=z(), byte_count=z(),
        t_min=jnp.full((n_shards, n_local), jnp.inf, jnp.float32),
        t_max=jnp.full((n_shards, n_local), -jnp.inf, jnp.float32),
        fwd_pkts=z(), rev_pkts=z(), fwd_bytes=z(), rev_bytes=z())
    state = ShardedFlowTable(
        regs=regs, epoch=jnp.full((n_shards,), jnp.inf, jnp.float32))
    if mesh is not None:
        state = jax.device_put(state, flow_table_sharding(mesh, state))
    return state


def localize_window(w: PacketWindow, n_shards: int, shard_idx):
    """Mask a replicated window down to one shard's packets.

    Returns (local_window, own (W,) bool): bucket ids remapped to local
    rows (b // n_shards — in range for every lane, owned or not) and
    valid restricted to owned lanes, so the unchanged single-device
    ``update_flow_table`` folds exactly the owned packets.
    """
    own = (w.bucket % n_shards) == shard_idx
    local = dataclasses.replace(w, bucket=w.bucket // n_shards,
                                valid=w.valid & own)
    return local, own


def shard_window_update(regs: FlowTableState, w: PacketWindow,
                        n_shards: int, shard_idx, *,
                        evict_age: Optional[float] = None,
                        saturate: bool = True,
                        evict_policy: str = "timeout",
                        lru_occupancy: float = 0.75,
                        readout: bool = True):
    """One shard's whole per-window register pass (shard_map body core).

    update (owned packets only) -> aging sweep -> overflow guard ->
    owner-masked readout of the window's touched rows. Returns
    (regs, epoch_min, own, x, n_evicted, n_overflow); x is (W, 8) with
    non-owned rows zeroed (None when readout=False), so psumming x-derived
    quantities across shards reconstructs the owner's value exactly.

    The aging sweep and overflow guard are the shared
    ``netsim.stream.lifecycle_sweep`` (pForest-style window aging, cutoff
    clamped to the window's oldest timestamp so flows seen this window
    always survive it) — one definition with the single-device tier, on
    which the bit-identity contract depends. ``evict_policy="approx_lru"``
    runs the pressure-triggered sweep *per shard*: occupancy and the
    score histogram are computed over this shard's local bucket block, so
    LRU decisions are shard-local — the sharded table under approx-LRU is
    NOT bit-identical to a single-device table of the global size (each
    shard defends its own slice, which is the deployment semantics of a
    partitioned flow table); the timeout policy keeps the bit-identity
    contract.
    """
    local, own = localize_window(w, n_shards, shard_idx)
    prev = regs                   # pre-update registers: the overflow guard
    regs = update_flow_table(regs, local)   # counts only newly saturated
    regs, n_ev, n_ov = lifecycle_sweep(regs, w, evict_age, saturate,
                                       prev=prev, evict_policy=evict_policy,
                                       lru_occupancy=lru_occupancy)
    x = None
    if readout:
        x = flow_table_readout(regs, local.bucket)          # (W, 8)
        x = jnp.where(own[:, None], x, 0.0)
    epoch = jnp.min(jnp.where(w.valid, w.ts, jnp.inf))
    return regs, epoch, own, x, n_ev, n_ov


def lane_slab_rows(n_lanes: int, n_shards: int, n_data: int = 1) -> int:
    """Static per-device lane tile: ceil(n_lanes / (n_shards * n_data)).

    The partitioned classify (DESIGN.md §16) pads the lane axis to
    ``T * n_shards * n_data`` rows so every device owns a fixed-shape
    slab regardless of which shard the traffic actually hashed to —
    ownership skew moves *values* between slabs, never shapes.
    """
    return -(-n_lanes // (n_shards * n_data))


def scatter_lane_slab(x: jax.Array, n_shards: int, n_data: int) -> jax.Array:
    """Owner-masked lane rows -> this device's complete lane slab.

    Runs under shard_map on the ('shard', 'data') mesh. ``x`` is the
    (N, F) per-shard readout with non-owned rows exactly zero, so the
    reduce-scatter over 'shard' sums one real row plus zeros per lane —
    complete rows, bit-identical to the owner's — and hands this shard
    the contiguous block [s*N/D_s : (s+1)*N/D_s). The 'data' index then
    slices that block into D_d equal slabs. Zero-padded tail lanes stay
    zero and are dropped by ``gather_lane_values``'s [:N].
    """
    n = x.shape[0]
    t = lane_slab_rows(n, n_shards, n_data)
    pad = t * n_shards * n_data - n
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    sl = jax.lax.psum_scatter(x, "shard", scatter_dimension=0, tiled=True)
    d = jax.lax.axis_index("data")
    return jax.lax.dynamic_slice_in_dim(sl, d * t, t)


def gather_lane_values(v: jax.Array, n_lanes: int) -> jax.Array:
    """Per-device slab results -> the replicated full lane vector.

    The tiled all_gather over ('shard', 'data') concatenates slabs
    shard-major / data-minor — exactly the order ``scatter_lane_slab``
    dealt them — so row i of the result is lane i's value; [:n_lanes]
    drops the even-division padding.
    """
    return jax.lax.all_gather(v, ("shard", "data"), tiled=True)[:n_lanes]


def stream_epoch(state: ShardedFlowTable) -> jax.Array:
    """True observed stream start in the provisional rebased frame.

    0.0 until any packet arrives, exactly 0.0 on an in-order stream whose
    provisional t0 was the first packet, and negative when the true start
    arrived after the host's latch — telemetry for mapping register
    timestamps back to wall clock (features never depend on it; they are
    epoch-invariant differences).
    """
    e = jnp.min(state.epoch)
    return jnp.where(jnp.isfinite(e), e, jnp.float32(0.0))


def sharded_flow_table(state: ShardedFlowTable) -> jax.Array:
    """(n_buckets, 8) canonical-bucket-order feature table.

    Gathers every shard's block back to the interleaved global order
    (row b = regs[b % D, b // D], i.e. ``leaf.T.reshape(-1)``) and derives
    features through the shared ``table_from_registers``. The raw
    t_min/t_max registers feed the derivation untouched — every feature
    is a timestamp difference, invariant to the rebase origin, and
    subtracting the epoch here would round duration bits differently
    than the serving-path readout does. Callers who need wall-clock flow
    times combine the registers with ``stream_epoch`` themselves.
    Test/telemetry path: serving reads out per-packet rows inside the
    shard_map instead.
    """
    flat = {f: getattr(state.regs, f).T.reshape(-1)
            for f in REGISTER_FIELDS}
    return table_from_registers(*[flat[f] for f in REGISTER_FIELDS])


# ---------------------------------------------------------------------------
# one-shot convenience / equivalence oracle
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=0)
def _sharded_update_step(state: ShardedFlowTable, w: PacketWindow,
                         mesh: Mesh, n_shards: int) -> ShardedFlowTable:
    def body(regs, epoch, w):
        sq = jax.tree.map(lambda a: a[0], regs)
        idx = jax.lax.axis_index("shard")
        # saturate=False: this is the equivalence oracle, and the batch /
        # single-device paths it is compared against never clamp — above
        # the 2^24 envelope both sides must be (in)exact identically
        sq, e, _, _, _, _ = shard_window_update(sq, w, n_shards, idx,
                                                saturate=False,
                                                readout=False)
        return (jax.tree.map(lambda a: a[None], sq),
                jnp.minimum(epoch, e))

    regs, epoch = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("shard", None), P("shard"), P()),
        out_specs=(P("shard", None), P("shard")))(
            state.regs, state.epoch, w)
    return ShardedFlowTable(regs=regs, epoch=epoch)


def stream_sharded_flow_features(trace, n_buckets=4096, window=1024, *,
                                 mesh: Optional[Mesh] = None,
                                 n_shards: Optional[int] = None,
                                 t0: Optional[float] = None):
    """Stream a trace through the sharded register file window by window.

    Returns (bucket_ids (P,), flow_table (n_buckets, 8)) in canonical
    bucket order — the sharded analog of ``stream_flow_features`` and the
    equivalence oracle of tests and ``benchmarks/shard_stream_bench.py``:
    bit-consistent with the batch ``flow_features`` whenever the rebase
    rounds identically under both epochs (always on in-order traces with
    the default t0; also under reordering, since registers are
    associative reductions and features epoch-invariant differences).
    """
    if mesh is None:
        mesh = flow_shard_mesh(n_shards)
    n_shards = mesh.shape["shard"]
    b = fnv1a_hash(trace.src_ip, trace.dst_ip, trace.sport, trace.dport,
                   trace.proto, n_buckets=n_buckets)
    state = init_sharded_table(n_buckets, mesh=mesh)
    for w in iter_windows(trace, window, n_buckets, bucket=b, t0=t0):
        state = _sharded_update_step(state, w, mesh, n_shards)
    return b, sharded_flow_table(state)
