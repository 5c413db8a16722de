"""Jit'd public wrappers over the Pallas kernels.

Handles batch padding to kernel tiles, dtype marshalling (quantized payloads
ride as exact f32), backend routing (Pallas on TPU, interpret-mode on CPU for
validation, or the XLA gather reference for speed), and the scalar epilogues
that turn kernel outputs into (pred, confidence).

The fused path consumes the artifact's pre-flattened single-matmul layout
(core.artifact.finalize_artifact); artifacts built by hand without it are
flattened on the fly, so every TableArtifact works.

VMEM fit check: the switch-SRAM analog. A model whose tables exceed the
budget is rejected for the fused kernel — same failure mode as a model that
doesn't fit the switch pipeline in the paper — and falls back to the XLA
path (the "run it on the host" situation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.artifact import (TableArtifact, build_dtable_flat,
                                 default_lane, flatten_ftable,
                                 flatten_vtable, pad_dtable,
                                 round_up_to_lane)
from repro.kernels import bucketize as _bk
from repro.kernels import ensemble_lookup as _ek
from repro.kernels import evict as _ev
from repro.kernels import classical_lookup as _ck
from repro.kernels import ref as _ref
from repro.kernels import stream_update as _su
from repro.kernels.tuning import (DEFAULT_TILES, TileConfig, padded_rows,
                                  resolve_use_pallas)

VMEM_BUDGET_BYTES = 8 * 1024 * 1024   # half of a v5e core's ~16MB VMEM


def _pad_batch(x, tile):
    """Pad N up to a tile multiple by replicating the last valid row.

    Replication (not zeros) keeps every padded lane on a real sample: a
    zero row is out-of-distribution for the tables and, in fused serving
    paths that compute telemetry before slicing, could perturb confidence
    statistics. A replicated row classifies identically to its source and
    is sliced off by [:n] like any pad.
    """
    n = x.shape[0]
    pad = (-n) % tile
    if pad:
        fill = jnp.broadcast_to(x[n - 1:n], (pad,) + x.shape[1:])
        x = jnp.concatenate([x, fill])
    return x, n


def pad_window(cols, tile: int):
    """Tile-pad per-packet columns to a multiple of ``tile``.

    ``cols`` is a pytree of arrays sharing leading length W0; returns
    (padded_cols, valid (Wp,) bool, n). Pad lanes replicate the last packet
    — in-distribution, the same discipline as ``_pad_batch`` — and carry
    valid=False, so streaming register updates and telemetry mask them out
    exactly. This is the streaming entry point: every window enters the
    jitted step at one static shape (``tile`` = the window size), so a
    ragged final window never recompiles and never perturbs flow state.
    """
    leaves = jax.tree.leaves(cols)
    n = leaves[0].shape[0]
    pad = (-n) % tile
    if pad:
        cols = jax.tree.map(
            lambda a: _pad_batch(jnp.asarray(a), tile)[0], cols)
    valid = jnp.arange(n + pad) < n
    return cols, valid, n


def evict_fill(regs, mask, fills, *, use_pallas=None, interpret=None):
    """Masked register reset: the eviction sweep's scatter.

    regs (R, N) f32 stacked register file, mask (N,) bool (True = evict),
    fills (R,) per-register reset identities -> (R, N). Evicted columns
    take their fill value, surviving columns pass through bit for bit.
    Pallas on TPU (``kernels.evict``), jnp.where elsewhere — the XLA form
    is what runs inside the shard_mapped streaming step on CPU meshes.
    """
    regs = jnp.asarray(regs, jnp.float32)
    fills = jnp.asarray(fills, jnp.float32)
    if not resolve_use_pallas(use_pallas):
        return jnp.where(mask[None, :], fills[:, None], regs)
    r, n = regs.shape
    tile = min(_ev.TILE_B, n) if n % _ev.TILE_B else _ev.TILE_B
    pad = (-n) % tile
    if pad:
        regs = jnp.pad(regs, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, (0, pad))       # pad columns: never evicted
    out = _ev.evict_fill_pallas(regs, mask, fills, interpret=interpret,
                                tile_b=tile)
    return out[:, :n]


def stream_update(regs, bucket, ts, length, is_fwd, valid, *, limit=None,
                  use_pallas=None, interpret=None):
    """Fused streaming register scatter + touched-row gather.

    regs (8, N) f32 stacked register file (``netsim.stream.
    REGISTER_FIELDS`` order); bucket/ts/length/is_fwd/valid the (W,)
    window columns -> (new_regs (8, N), rows (8, W)): the window folded
    into the registers (count registers clamped at ``limit`` when given
    — the 2^24 overflow guard) and each lane's updated register row.
    Pallas on TPU (``kernels.stream_update``: one VMEM pass per bucket
    tile, no HBM round-trip between scatter and gather), the XLA
    segment/gather reference elsewhere — bit-identical by the
    integer-exactness/associativity argument in the kernel docstring.
    """
    regs = jnp.asarray(regs, jnp.float32)
    if not resolve_use_pallas(use_pallas):
        return _ref.stream_update_ref(regs, bucket, ts, length, is_fwd,
                                      valid, limit=limit)
    r, n = regs.shape
    tile = min(_su.TILE_B, n) if n % _su.TILE_B else _su.TILE_B
    pad = (-n) % tile
    if pad:
        regs = jnp.pad(regs, ((0, 0), (0, pad)))   # bucket < n: never matched
    new_regs, rows = _su.stream_update_pallas(
        regs, bucket, ts, length, is_fwd, valid, limit=limit,
        interpret=interpret, tile_b=tile)
    return new_regs[:, :n], rows


def bucketize(x, edges, *, use_pallas=None):
    """Public bucketize. x (N, F), edges (F, U) -> (N, F) int32."""
    if not resolve_use_pallas(use_pallas):
        return _ref.bucketize_ref(x, edges)
    xp, n = _pad_batch(jnp.asarray(x, jnp.float32), _bk.TILE_N)
    return _bk.bucketize_pallas(xp, edges)[:n]


def _flat_tree_tables(art: TableArtifact, vote: bool):
    """Pre-flattened tables from the artifact, or on-the-fly fallback."""
    if art.ftable_flat is not None:
        return art.ftable_flat, art.dtable_flat, art.dtable_pad
    dtable = art.dtable_class if vote else art.dtable_value.q
    return (flatten_ftable(art.ftable, art.strides),
            build_dtable_flat(dtable, art.n_classes, vote),
            pad_dtable(dtable))


def _flat_vtable(art: TableArtifact):
    if art.vtable_flat is not None:
        return art.vtable_flat
    return flatten_vtable(art.vtable.q)


def tree_tables_vmem_bytes(art: TableArtifact) -> int:
    """Bytes the fused kernel will actually hold in VMEM — i.e. the
    lane-padded flat layout, whether it is pre-built on the artifact or
    about to be flattened on the fly. Only one decision table (flat or
    pad) is a kernel operand, chosen by the same crossover as
    select='auto' — mirror it so large-table models that would run the
    compare strategy are not rejected for the matmul table they'd never
    load."""
    e = art.edges.size * 4
    if art.ftable_flat is not None:
        f = art.ftable_flat.size * 4
        cout, t, s_pad = art.dtable_flat.shape
    else:
        lane = default_lane()
        fdim, b, t = art.ftable.shape
        s_pad = round_up_to_lane(art.dtable_class.shape[1], lane)
        cout = art.n_classes if art.agg == "vote" else 1
        f = (fdim * round_up_to_lane(b, lane)
             * round_up_to_lane(t, lane) * 4)
    matmul_select = t * s_pad * cout <= _ek.SELECT_MATMUL_MAX
    d = (cout if matmul_select else 1) * t * s_pad * 4
    return e + f + d


def _vtable_vmem_bytes(art: TableArtifact) -> int:
    if art.vtable_flat is not None:
        return art.vtable_flat.size * 4
    lane = default_lane()
    fdim, b, m = art.vtable.q.shape
    return fdim * round_up_to_lane(b, lane) * round_up_to_lane(m, lane) * 4


def fits_vmem(art: TableArtifact) -> bool:
    if art.ftable is None:
        return (art.edges.size * 4 + _vtable_vmem_bytes(art)
                <= VMEM_BUDGET_BYTES)
    return tree_tables_vmem_bytes(art) <= VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# fused classify
# ---------------------------------------------------------------------------

def _tree_epilogue(art: TableArtifact, out):
    if art.agg == "vote":
        votes = out                                         # (N, C)
        pred = jnp.argmax(votes, axis=1)
        conf = jnp.max(votes, axis=1) / art.n_trees
        return pred, conf
    total = out[:, 0] / art.dtable_value.scale
    if art.agg == "wsum_sigmoid":
        p1 = jax.nn.sigmoid(art.base_score + art.learning_rate * total)
        return (p1 > 0.5).astype(jnp.int32), jnp.maximum(p1, 1 - p1)
    if art.agg == "iforest":
        n = jnp.float32(art.iforest_subsample)
        cfac = 2.0 * (jnp.log(n - 1.0) + 0.5772156649) - 2.0 * (n - 1.0) / n
        score = 2.0 ** (-(total / art.n_trees) / cfac)
        return (score > 0.5).astype(jnp.int32), jnp.maximum(score, 1 - score)
    raise ValueError(art.agg)


def _classical_epilogue(art: TableArtifact, out):
    total = out / art.vtable.scale                          # (N, M)
    if art.agg == "svm_ovo":
        planes = total + art.consts[None, :]
        win_i = planes > 0
        votes = jnp.zeros((planes.shape[0], art.n_classes), jnp.float32)
        votes = votes.at[:, art.pairs[:, 0]].add(win_i.astype(jnp.float32))
        votes = votes.at[:, art.pairs[:, 1]].add((~win_i).astype(jnp.float32))
        pred = jnp.argmax(votes, axis=1)
        if planes.shape[1] == 1:
            conf = jax.nn.sigmoid(2.0 * jnp.abs(planes[:, 0]))
        else:
            conf = jnp.max(votes, axis=1) / planes.shape[1]
        return pred, conf
    if art.agg == "nb_log":
        joint = total + art.consts[None, :]
        return (jnp.argmax(joint, axis=1),
                jnp.max(jax.nn.softmax(joint, axis=1), axis=1))
    if art.agg == "kmeans":
        pred = jnp.argmin(total, axis=1)
        top2 = jax.lax.top_k(-total, 2)[0]
        return pred, 1.0 - jnp.exp(top2[:, 1] - top2[:, 0])
    raise ValueError(art.agg)


def classify_impl(art: TableArtifact, *, use_pallas=None,
                  tiles: TileConfig = None) -> str:
    """The realization ``fused_classify`` runs for this artifact:
    ``tiles.impl`` ('fused' by default, or 'loop') when the Pallas
    kernels are on and the tables fit the VMEM budget, else 'ref' (the
    XLA gather reference). The one place the routing is decided, so a
    caller can see when a TPU run would leave the kernel."""
    tiles = tiles or DEFAULT_TILES
    if resolve_use_pallas(use_pallas) and fits_vmem(art):
        return tiles.impl
    return "ref"


def classify_batch_rows(art: TableArtifact, n: int, *, use_pallas=None,
                        tiles: TileConfig = None) -> int:
    """Rows ``fused_classify`` actually processes for an n-row batch.

    The fused/loop Pallas realizations pad the batch to their tile
    granularity (``_pad_batch``); the XLA reference processes exactly n.
    Mirrors the routing in ``fused_classify`` so callers reporting
    per-device classify work (the shard bench's classify_rows_per_device
    gate) count the kernel's real row count, not the logical one.
    """
    tiles = tiles or DEFAULT_TILES
    impl = classify_impl(art, use_pallas=use_pallas, tiles=tiles)
    if impl == "fused":
        return padded_rows(n, tiles.tile_n)
    if impl == "loop":
        return padded_rows(n, _ek.TILE_N)
    return n


def fused_classify(art: TableArtifact, x, *, use_pallas=None,
                   interpret=None, tiles: TileConfig = None):
    """(pred, confidence) through the fused kernel path.

    use_pallas=None auto-routes: Pallas on TPU, XLA reference otherwise.
    Pass use_pallas=True on CPU to exercise interpret mode (tests do).
    tiles overrides the kernel tile sizes (see kernels.tuning.autotune_tiles)
    and the realization: ``tiles.impl`` picks the fused single-matmul
    kernel (default), the per-feature-loop kernel ('loop', tree artifacts
    only) or the XLA gather reference ('ref') — all bit-identical, so the
    autotuner is free to pick whichever is fastest for the artifact shape.
    """
    tiles = tiles or DEFAULT_TILES
    x = jnp.asarray(x, jnp.float32)
    impl = classify_impl(art, use_pallas=use_pallas, tiles=tiles)

    if art.ftable is not None:
        vote = art.agg == "vote"
        if impl == "fused":
            ftable_flat, dtable_flat, dtable_pad = _flat_tree_tables(art, vote)
            xp, n = _pad_batch(x, tiles.tile_n)
            out = _ek.ensemble_lookup_fused(
                xp, art.edges, ftable_flat, dtable_flat, dtable_pad,
                interpret=interpret, tile_n=tiles.tile_n,
                edge_chunk=tiles.edge_chunk,
                dtable_chunk=tiles.dtable_chunk,
                select=tiles.select)[:n]
        else:
            dtable = (art.dtable_class if vote else art.dtable_value.q)
            if impl == "loop":
                xp, n = _pad_batch(x, _ek.TILE_N)
                out = _ek.ensemble_lookup_pallas_loop(
                    xp, art.edges, art.ftable, art.strides,
                    dtable.astype(jnp.float32), n_classes=art.n_classes,
                    vote=vote, interpret=interpret)[:n]
            else:
                out = _ref.ensemble_lookup_ref(
                    x, art.edges, art.ftable, art.strides,
                    dtable.astype(jnp.float32),
                    n_classes=art.n_classes, vote=vote)
        return _tree_epilogue(art, out)

    if impl == "loop":
        raise ValueError("impl='loop' is the per-feature-loop tree kernel; "
                         "classical artifacts have no loop realization")
    m = art.vtable.q.shape[2]
    if impl == "fused":
        xp, n = _pad_batch(x, tiles.tile_n)
        out = _ck.classical_lookup_fused(
            xp, art.edges, _flat_vtable(art), interpret=interpret,
            tile_n=tiles.tile_n, edge_chunk=tiles.edge_chunk)[:n, :m]
    else:
        out = _ref.classical_lookup_ref(x, art.edges,
                                        art.vtable.q.astype(jnp.float32))
    return _classical_epilogue(art, out)
