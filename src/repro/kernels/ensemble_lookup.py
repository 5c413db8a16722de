"""Pallas TPU kernel: the fused IIsy match-action pipeline (tree family).

One kernel = the whole switch pipeline:

  1. range match         bins[n,f] = #{u : x[n,f] > edges[f,u]}        (VPU)
  2. feature tables +    keys[n,t] = sum_f ftable[f, bins[n,f], t] * strides[t,f]
     decision key        -> ONE blocked one-hot matmul: the (TN, F) bins
                            become a (TN, F*Bp) blocked one-hot (offset iota,
                            no per-feature loop) and the whole feature-table
                            walk is a single MXU matmul against the
                            stride-premultiplied flat table (F*Bp, Tp) built
                            by core.artifact.finalize_artifact. On TPU a
                            lookup table IS a matmul with a one-hot key —
                            here ALL F lookups and the mixed-radix combine
                            are one systolic pass.
  3. decision tables     leaf[n,t] = dtable[t, keys[n,t]]
     + aggregation       votes[n,c] = #{t : leaf class == c}  (vote)
                         total[n]   = sum_t leaf value         (sum aggs)
                         -> ONE more matmul. The TCAM-style parallel
                            compare (every entry matched against the key
                            simultaneously, what TCAM silicon does) builds a
                            match one-hot over (T, Sp); contracting it with
                            the precomputed aggregation table
                            dtable_flat[c, t, s] (one-hot of leaf classes,
                            or leaf payloads) yields votes/totals directly:
                            out[n,c] = sum_{t,s} match[n,t,s]*dflat[c,t,s].
                            Select and aggregate never materialize per-tree
                            leaves — they are one systolic pass, chunked
                            over Sp to bound the match intermediate.

All tables stay fully VMEM-resident across the grid — the VMEM budget plays
the switch-SRAM role (artifact_resources() decides fit, like Tables 1-2).
The scalar epilogue (argmax / sigmoid / iforest score) runs in kernels/ops.py.

Integer payloads ride as f32 (exact below 2^24), so the MXU path needs no
integer matmul support and quantized sums stay bit-exact vs the oracle.

``ensemble_lookup_pallas_loop`` keeps the previous per-feature-loop kernel
(F small matmuls in a Python loop) as the microbenchmark baseline —
benchmarks/kernel_microbench.py records the fused-vs-loop speedup.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.artifact import build_dtable_flat, flatten_ftable, pad_dtable
from repro.kernels.tuning import (DEFAULT_TILES, EXACT_F32,
                                  resolve_interpret)

TILE_N = DEFAULT_TILES.tile_n
EDGE_CHUNK = DEFAULT_TILES.edge_chunk
DTABLE_CHUNK = DEFAULT_TILES.dtable_chunk

# select='auto' crossover: the matmul select touches T*Sp*Co MACs per row,
# the compare select T*Sp wheres plus a per-tree leaf pass — so the
# crossover is on T*Sp*Co. Measured on CPU and sized for VMEM, the matmul
# wins while the whole flat decision table stays within a couple of
# MXU-sized chunks per row.
SELECT_MATMUL_MAX = 8192


def _range_match(x, edges_ref, u_total, edge_chunk=EDGE_CHUNK):
    """bins[n,f] = #{u : x[n,f] > edges[f,u]} — chunked compare sweep."""
    tn, f = x.shape
    bins = jnp.zeros((tn, f), jnp.int32)
    for c in range(pl.cdiv(u_total, edge_chunk)):
        lo = c * edge_chunk
        hi = min(lo + edge_chunk, u_total)
        e = edges_ref[:, lo:hi]                             # (F, cu)
        bins = bins + jnp.sum(
            (x[:, :, None] > e[None, :, :]).astype(jnp.int32), axis=2)
    return bins


def _blocked_one_hot(bins, b_pad):
    """(TN, F) bins -> (TN, F*Bp) blocked one-hot (feature f owns lanes
    [f*Bp, (f+1)*Bp)). bins <= U < Bp, so padded lanes are never hot."""
    tn, f = bins.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, b_pad), 2)
    oh = (bins[:, :, None] == iota).astype(jnp.float32)     # (TN, F, Bp)
    return oh.reshape(tn, f * b_pad)


def _match_agg(keys_i, dflat_ref, dtable_chunk):
    """Decision select + aggregation as one chunked matmul.

    out[n, c] = sum_{t,s} (keys[n,t] == s) * dflat[c, t, s]. The match
    one-hot is the TCAM compare; the contraction against the precomputed
    aggregation table does lookup AND vote-count/payload-sum in one MXU
    pass. Padded entries (index >= logical S) can never match: keys < S.
    """
    tn, t = keys_i.shape
    cout, _, s_pad = dflat_ref.shape
    out = jnp.zeros((tn, cout), jnp.float32)
    for c in range(pl.cdiv(s_pad, dtable_chunk)):
        lo = c * dtable_chunk
        hi = min(lo + dtable_chunk, s_pad)
        s_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, hi - lo), 2) + lo
        match = (keys_i[:, :, None] == s_iota).astype(jnp.float32)
        match = match.reshape(tn, t * (hi - lo))            # (TN, T*cs)
        dflat = dflat_ref[:, :, lo:hi].reshape(cout, t * (hi - lo))
        out = out + jax.lax.dot_general(
            match, dflat, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=EXACT_F32)                            # (TN, Co)
    return out


def _fused_kernel(x_ref, edges_ref, ftab_ref, dflat_ref, out_ref, *,
                  u_total: int, t_logical: int, edge_chunk: int,
                  dtable_chunk: int):
    x = x_ref[...]                                          # (TN, F)
    tn, f = x.shape
    b_pad = ftab_ref.shape[0] // f

    bins = _range_match(x, edges_ref, u_total, edge_chunk)

    # stages 2+3 as ONE matmul: the flat table is stride-premultiplied, so
    # the matmul performs all F lookups AND the mixed-radix key combine.
    oh = _blocked_one_hot(bins, b_pad)                      # (TN, F*Bp)
    keys = jax.lax.dot(oh, ftab_ref[...],
                       preferred_element_type=jnp.float32,
                       precision=EXACT_F32)                 # (TN, Tp)
    keys_i = keys[:, :t_logical].astype(jnp.int32)          # exact below 2^24

    # stages 4+5 as one more matmul: select + aggregate
    out_ref[...] = _match_agg(keys_i, dflat_ref, dtable_chunk)


def _fused_compare_kernel(x_ref, edges_ref, ftab_ref, dtable_ref, out_ref, *,
                          u_total: int, t_logical: int, n_classes: int,
                          vote: bool, edge_chunk: int, dtable_chunk: int):
    """Fused stage-2 matmul + compare-select decision stage.

    For large T*Sp the match one-hot of the matmul select costs more than
    TCAM-style where/sum over the raw (T, Sp) table; this variant keeps the
    single-matmul feature-table walk and selects leaves the seed way.
    """
    x = x_ref[...]                                          # (TN, F)
    tn, f = x.shape
    b_pad = ftab_ref.shape[0] // f
    s_pad = dtable_ref.shape[1]

    bins = _range_match(x, edges_ref, u_total, edge_chunk)
    oh = _blocked_one_hot(bins, b_pad)                      # (TN, F*Bp)
    keys = jax.lax.dot(oh, ftab_ref[...],
                       preferred_element_type=jnp.float32,
                       precision=EXACT_F32)                 # (TN, Tp)
    keys_i = keys[:, :t_logical].astype(jnp.int32)

    leaf = jnp.zeros((tn, t_logical), jnp.float32)
    for c in range(pl.cdiv(s_pad, dtable_chunk)):
        lo = c * dtable_chunk
        hi = min(lo + dtable_chunk, s_pad)
        s_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, hi - lo), 2) + lo
        match = (keys_i[:, :, None] == s_iota)              # (TN, T, cs)
        dt = dtable_ref[:, lo:hi]                           # (T, cs)
        leaf = leaf + jnp.sum(jnp.where(match, dt[None, :, :], 0.0), axis=2)

    if vote:
        c_iota = jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, n_classes), 2).astype(jnp.float32)
        out_ref[...] = jnp.sum(
            (leaf[:, :, None] == c_iota).astype(jnp.float32), axis=1)
    else:
        out_ref[...] = jnp.sum(leaf, axis=1, keepdims=True)


def ensemble_lookup_fused(x, edges, ftable_flat, dtable_flat, dtable_pad, *,
                          interpret=None, tile_n=None, edge_chunk=None,
                          dtable_chunk=None, select: str = "auto"
                          ) -> jax.Array:
    """Single-matmul fused pipeline on pre-flattened tables.

    x (N, F) f32 with N % tile_n == 0; edges (F, U) f32;
    ftable_flat (F*Bp, Tp) f32 stride-premultiplied (finalize_artifact);
    dtable_flat (Co, T, Sp) f32 decision+aggregation table;
    dtable_pad (T, Sp) f32 raw decision table (compare-select strategy).
    select: 'matmul' | 'compare' | 'auto' (matmul while T*Sp is small
    enough that the match one-hot contraction beats TCAM where/sum).
    Returns (N, Co): per-class votes (vote) or payload sums (Co == 1).
    """
    interpret = resolve_interpret(interpret)
    tile_n = tile_n or TILE_N
    edge_chunk = edge_chunk or EDGE_CHUNK
    dtable_chunk = dtable_chunk or DTABLE_CHUNK
    n, f = x.shape
    u = edges.shape[1]
    fb, t_pad = ftable_flat.shape
    cout, t, s_pad = dtable_flat.shape
    assert n % tile_n == 0, (n, tile_n)
    if select == "auto":
        select = ("matmul" if t * s_pad * cout <= SELECT_MATMUL_MAX
                  else "compare")
    if select == "matmul":
        kernel = functools.partial(_fused_kernel, u_total=u, t_logical=t,
                                   edge_chunk=edge_chunk,
                                   dtable_chunk=dtable_chunk)
        dtable_in = dtable_flat
        dtable_spec = pl.BlockSpec((cout, t, s_pad), lambda i: (0, 0, 0))
    else:
        kernel = functools.partial(_fused_compare_kernel, u_total=u,
                                   t_logical=t, n_classes=cout,
                                   vote=cout > 1, edge_chunk=edge_chunk,
                                   dtable_chunk=dtable_chunk)
        dtable_in = dtable_pad
        dtable_spec = pl.BlockSpec((t, s_pad), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, f), lambda i: (i, 0)),
            pl.BlockSpec((f, u), lambda i: (0, 0)),
            pl.BlockSpec((fb, t_pad), lambda i: (0, 0)),
            dtable_spec,
        ],
        out_specs=pl.BlockSpec((tile_n, cout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, cout), jnp.float32),
        interpret=interpret,
    )(x, edges, ftable_flat, dtable_in)


def ensemble_lookup_pallas(x, edges, ftable, strides, dtable, *,
                           n_classes: int, vote: bool, interpret=None,
                           tile_n=None, edge_chunk=None, dtable_chunk=None,
                           select: str = "auto") -> jax.Array:
    """Run the fused pipeline from unflattened tables (compat entry).

    Flattens ftable/strides/dtable into the single-matmul layout on the fly
    (serving uses the artifact's pre-flattened copies instead). Shapes:
    x (N, F) f32 with N % tile_n == 0; edges (F, U) f32; ftable (F, U+1, T)
    int32; strides (T, F) int32; dtable (T, S) f32 (class ids or quantized
    payload as exact floats). interpret=None auto-detects the backend.
    Returns (N, n_classes) votes or (N, 1) sums, as before.
    """
    ftable_flat = flatten_ftable(ftable, strides)
    dtable_flat = build_dtable_flat(dtable, n_classes, vote)
    dtable_padded = pad_dtable(dtable)
    return ensemble_lookup_fused(
        x, edges, ftable_flat, dtable_flat, dtable_padded,
        interpret=interpret, tile_n=tile_n, edge_chunk=edge_chunk,
        dtable_chunk=dtable_chunk, select=select)


# ---------------------------------------------------------------------------
# legacy per-feature-loop kernel — kept as the microbenchmark baseline
# ---------------------------------------------------------------------------

def _loop_kernel(x_ref, edges_ref, ftable_ref, strides_ref, dtable_ref,
                 out_ref, *, u_total: int, s_total: int, n_classes: int,
                 vote: bool):
    x = x_ref[...]                                          # (TN, F)
    tn, f = x.shape
    t = strides_ref.shape[0]
    n_bins = u_total + 1

    bins = _range_match(x, edges_ref, u_total)

    # stages 2+3 as F separate small matmuls (the pre-fusion formulation)
    keys = jnp.zeros((tn, t), jnp.float32)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_bins), 1)
    for fi in range(f):                                     # static unroll
        oh = (bins[:, fi][:, None] == b_iota).astype(jnp.float32)  # (TN, B)
        ft = ftable_ref[fi].astype(jnp.float32)             # (B, T)
        code = jax.lax.dot(oh, ft,
                           preferred_element_type=jnp.float32,
                           precision=EXACT_F32)             # (TN, T)
        keys = keys + code * strides_ref[:, fi].astype(jnp.float32)[None, :]
    keys_i = keys.astype(jnp.int32)

    # stage 4: TCAM compare-select, then a separate aggregation pass
    leaf = jnp.zeros((tn, t), jnp.float32)
    for c in range(pl.cdiv(s_total, DTABLE_CHUNK)):
        lo = c * DTABLE_CHUNK
        hi = min(lo + DTABLE_CHUNK, s_total)
        s_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, hi - lo), 2) + lo
        match = (keys_i[:, :, None] == s_iota)              # (TN, T, cs)
        dt = dtable_ref[:, lo:hi].astype(jnp.float32)       # (T, cs)
        leaf = leaf + jnp.sum(jnp.where(match, dt[None, :, :], 0.0), axis=2)

    if vote:
        c_iota = jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, n_classes), 2).astype(jnp.float32)
        votes = jnp.sum((leaf[:, :, None] == c_iota).astype(jnp.float32),
                        axis=1)                             # (TN, C)
        out_ref[...] = votes
    else:
        out_ref[...] = jnp.sum(leaf, axis=1, keepdims=True)


def ensemble_lookup_pallas_loop(x, edges, ftable, strides, dtable, *,
                                n_classes: int, vote: bool,
                                interpret=None) -> jax.Array:
    """Per-feature-loop variant (F small matmuls). Baseline only — use
    ensemble_lookup_pallas / ensemble_lookup_fused in real code."""
    interpret = resolve_interpret(interpret)
    n, f = x.shape
    u = edges.shape[1]
    t, s = dtable.shape
    assert n % TILE_N == 0, n
    out_cols = n_classes if vote else 1
    kernel = functools.partial(_loop_kernel, u_total=u, s_total=s,
                               n_classes=n_classes, vote=vote)
    return pl.pallas_call(
        kernel,
        grid=(n // TILE_N,),
        in_specs=[
            pl.BlockSpec((TILE_N, f), lambda i: (i, 0)),
            pl.BlockSpec((f, u), lambda i: (0, 0)),
            pl.BlockSpec((f, u + 1, t), lambda i: (0, 0, 0)),
            pl.BlockSpec((t, f), lambda i: (0, 0)),
            pl.BlockSpec((t, s), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, out_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_cols), jnp.float32),
        interpret=interpret,
    )(x, edges, ftable, strides, dtable)
