"""Hybrid deployment (§2.2.1, §7.7): small switch model + large backend.

``hybrid_predict`` is the analysis-friendly dense form used by the paper's
sweeps (Figs 10-11). ``dispatch``/``combine`` are the serving form: the
low-confidence subset is *compacted* (a stable sort puts the forwarded
rows first in a fixed-capacity buffer) so the expensive backend only sees
the forwarded queries — the load-reduction benefit in collective/compute
terms — and ``backend_over_blocks`` runs the backend
only on the buffer's row blocks that hold them.

Every form takes ``switch_features``: the static column indices the switch
parses out of a wider row (the finance deployment's 5 of 130). The switch
classifies those columns; the backend gets the whole row. ``None`` means
the switch reads every column.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.artifact import TableArtifact
from repro.core.inference import table_predict


@dataclasses.dataclass
class HybridResult:
    pred: jax.Array          # (N,) final classes
    switch_pred: jax.Array   # (N,) switch-tier classes
    confidence: jax.Array    # (N,)
    handled: jax.Array       # (N,) bool: True = answered at the switch
    fraction_handled: jax.Array


def switch_columns(x: jax.Array,
                   switch_features: Optional[Sequence[int]]) -> jax.Array:
    """The columns of ``x`` (N, F) the switch parses: ``x`` itself for
    ``None``, else the listed columns in order, as static slices (no
    gather)."""
    if switch_features is None:
        return x
    return jnp.concatenate([x[:, c:c + 1] for c in switch_features], axis=1)


def hybrid_predict(art: TableArtifact, backend_fn: Callable, x,
                   threshold: float,
                   switch_features: Optional[Sequence[int]] = None
                   ) -> HybridResult:
    """Dense hybrid: backend evaluated everywhere, selected where needed."""
    sw_pred, conf = table_predict(art, switch_columns(x, switch_features))
    handled = conf >= threshold
    be_pred = backend_fn(x)
    pred = jnp.where(handled, sw_pred, be_pred)
    return HybridResult(pred=pred, switch_pred=sw_pred, confidence=conf,
                        handled=handled,
                        fraction_handled=jnp.mean(handled.astype(jnp.float32)))


def dispatch(x: jax.Array, forward_mask: jax.Array, capacity: int):
    """Compact the forwarded rows into a fixed-capacity buffer.

    Returns (buf (capacity, F), idx (capacity,), valid (capacity,)).
    Rows beyond capacity are dropped from forwarding (the switch would answer
    them itself under congestion — paper §7.1.2's trade-off); callers keep the
    switch prediction for them.
    """
    n = x.shape[0]
    order = jnp.argsort(~forward_mask, stable=True)        # forwarded first
    idx = order[:capacity]
    valid = forward_mask[idx]
    buf = x[idx]
    return buf, idx, valid


# Rows per backend block in the fused step: the TPU's lane width, which is
# also the smallest row block of ``repro.ml.trees``' walk. A dispatch buffer
# whose capacity is not a multiple of it is one block.
BACKEND_BLOCK = 128


def backend_block(capacity: int) -> int:
    """Rows per backend block of a ``capacity``-row dispatch buffer."""
    return BACKEND_BLOCK if capacity % BACKEND_BLOCK == 0 else capacity


def backend_over_blocks(backend_fn: Callable, buf: jax.Array,
                        rows: jax.Array) -> jax.Array:
    """``backend_fn`` over the blocks of ``buf`` (capacity, F) that hold
    forwarded rows -> answers (capacity, ...).

    ``dispatch`` puts the forwarded rows first, so the ``rows`` valid ones
    (a traced count: no recompile per count) lie in the first
    ``ceil(rows / B)`` blocks of ``B = backend_block(capacity)`` rows. A
    loop runs ``backend_fn`` on each such (B, F) block, traced once; the
    other blocks keep zero answers, which ``combine`` never reads (their
    ``valid`` is False). With ``rows`` 0 the backend does not run."""
    cap = buf.shape[0]
    b = backend_block(cap)

    def block(r):
        return jnp.asarray(backend_fn(r))

    out = jax.eval_shape(
        block, jax.ShapeDtypeStruct((b,) + buf.shape[1:], buf.dtype))
    ans = jnp.zeros((cap,) + out.shape[1:], out.dtype)

    def body(i, ans):
        start = i * b
        return jax.lax.dynamic_update_slice_in_dim(
            ans, block(jax.lax.dynamic_slice_in_dim(buf, start, b)), start,
            axis=0)

    return jax.lax.fori_loop(0, (rows + b - 1) // b, body, ans)


def combine(switch_pred: jax.Array, backend_pred_subset: jax.Array,
            idx: jax.Array, valid: jax.Array) -> jax.Array:
    """Scatter backend answers for forwarded rows back over switch answers."""
    upd = jnp.where(valid, backend_pred_subset, switch_pred[idx])
    return switch_pred.at[idx].set(upd)


# ---------------------------------------------------------------------------
# cross-window deferred dispatch (DESIGN.md §7)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeferredDispatch:
    """Device-resident deferral buffer for cross-window backend batching.

    Instead of paying one backend invocation per window for at most
    ``capacity`` rows, serving defers the compacted low-confidence rows of
    up to ``flush_every`` windows into this buffer and runs the backend
    once per flush at ``flush_every``-times the occupancy. Each slot keeps
    its *return address* — ``(window, lane)``: the pending-cycle slot the
    row came from and its lane within that window — so a flush can
    back-patch the backend answers into the per-window pending prediction
    set (``backpatch_pending``).

    ``buf`` is ``(flush_every * capacity, F)`` on a single device, or
    ``(n_shards, flush_every * capacity, F)`` on the sharded tier, where
    every shard accumulates the partial rows it owns (non-owner lanes
    zero) and a flush reduce-scatters complete rows so each shard's
    backend serves only its slice. Donation discipline matches the other
    serving carries: the buffer is donated into every defer/flush step —
    callers never hold a reference to a previous one.
    """
    buf: jax.Array       # (k*cap, F) or (n_shards, k*cap, F) deferred rows
    lane: jax.Array      # (k*cap,) i32 lane within the source window
    window: jax.Array    # (k*cap,) i32 pending-cycle slot in [0, flush_every)
    valid: jax.Array     # (k*cap,) bool: slot holds a live deferred row

    @property
    def slots(self) -> int:
        return self.lane.shape[0]


def init_deferred(flush_every: int, capacity: int, n_features: int, *,
                  n_shards: int = None) -> DeferredDispatch:
    """Empty deferral buffer for ``flush_every`` windows of ``capacity``
    rows each. ``n_shards`` adds the leading shard dim of the sharded
    tier's partial-row accumulation buffer."""
    n = flush_every * capacity
    shape = (n, n_features) if n_shards is None else (n_shards, n, n_features)
    return DeferredDispatch(
        buf=jnp.zeros(shape, jnp.float32),
        lane=jnp.zeros((n,), jnp.int32),
        window=jnp.zeros((n,), jnp.int32),
        valid=jnp.zeros((n,), bool))


def defer_window(dd: DeferredDispatch, buf: jax.Array, idx: jax.Array,
                 valid: jax.Array, pos) -> DeferredDispatch:
    """Append one window's dispatched rows at pending-cycle slot ``pos``.

    ``buf``/``idx``/``valid`` are ``dispatch``'s outputs for the window
    (the sharded tier passes its per-shard partial ``(n_shards, capacity,
    F)`` buffer); ``pos`` is a traced i32 scalar, so stepping through the
    cycle never recompiles. Slot ``pos`` occupies rows
    ``[pos*capacity, (pos+1)*capacity)``.
    """
    cap = idx.shape[0]
    row0 = pos * cap
    if dd.buf.ndim == 3:
        new_buf = jax.lax.dynamic_update_slice(dd.buf, buf, (0, row0, 0))
    else:
        new_buf = jax.lax.dynamic_update_slice(dd.buf, buf, (row0, 0))
    return DeferredDispatch(
        buf=new_buf,
        lane=jax.lax.dynamic_update_slice(
            dd.lane, idx.astype(jnp.int32), (row0,)),
        window=jax.lax.dynamic_update_slice(
            dd.window, jnp.full((cap,), pos, jnp.int32), (row0,)),
        valid=jax.lax.dynamic_update_slice(dd.valid, valid, (row0,)))


def chunk_dispatch(xs: jax.Array, fwd: jax.Array,
                   capacity: int) -> DeferredDispatch:
    """Vectorized per-window dispatch over a whole chunk of windows.

    xs (K, W, F) feature rows, fwd (K, W) forward masks -> one
    ``DeferredDispatch`` covering the chunk: ``dispatch`` vmapped over
    the window axis (every window still capacity-bounded exactly as the
    per-window path bounds it — the bit-equality contract of the chunked
    megastep), the (window, lane) return addresses laid out row-major so
    slot ``k*capacity + i`` is window k's i-th dispatched row. Built in
    one shot from stacked scan outputs — nothing is carried through the
    scan and no per-window buffer writes happen; ``backpatch_pending``
    consumes it unchanged.
    """
    k, w, f = xs.shape
    buf, idx, valid = jax.vmap(lambda x1, f1: dispatch(x1, f1, capacity))(
        xs, fwd)
    return DeferredDispatch(
        buf=buf.reshape(k * capacity, f),
        lane=idx.reshape(-1).astype(jnp.int32),
        window=jnp.repeat(jnp.arange(k, dtype=jnp.int32), capacity),
        valid=valid.reshape(-1))


def backpatch_pending(pending: jax.Array, backend_pred: jax.Array,
                      dd: DeferredDispatch) -> jax.Array:
    """Scatter flushed backend answers into the per-window pending set.

    ``pending`` is the ``(flush_every, W)`` prediction buffer holding each
    pending window's switch answers; every live deferral slot overwrites
    its ``(window, lane)`` return address with the backend's answer.
    Dead slots are routed out of bounds and dropped, so a partially
    filled cycle (the guaranteed end-of-trace flush) patches exactly the
    rows that were deferred. Live addresses are unique by construction
    (lanes are distinct within a window, cycle slots distinct across
    windows), so the scatter is deterministic.
    """
    row = jnp.where(dd.valid, dd.window, pending.shape[0])
    return pending.at[row, dd.lane].set(
        backend_pred.astype(pending.dtype), mode="drop")


def hybrid_serve(art: TableArtifact, backend_fn: Callable, x,
                 threshold: float, capacity: int,
                 switch_features: Optional[Sequence[int]] = None):
    """Serving-form hybrid with bounded backend batch.

    backend_fn receives exactly ``capacity`` full-width rows (padded with
    whatever rows were not forwarded) — a static shape, so the backend
    step stays jittable.
    """
    sw_pred, conf = table_predict(art, switch_columns(x, switch_features))
    fwd = conf < threshold
    buf, idx, valid = dispatch(x, fwd, capacity)
    be_pred = backend_fn(buf)
    pred = combine(sw_pred, be_pred, idx, valid)
    return pred, jnp.mean(fwd.astype(jnp.float32))
