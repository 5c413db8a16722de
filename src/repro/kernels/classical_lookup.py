"""Pallas TPU kernel: fused classical-model pipeline (SVM / NB / K-Means).

The §4.3 "table per feature" mapping: each feature's bin holds a quantized
partial term vector (a_j*x for SVM planes, log P(x|c) for NB, (x-c)^2 for
K-Means); the pipeline sums them. Fused as ONE matmul:

  out[n, m] = sum_f vtable[f, bins[n, f], m]
            = blocked_onehot(bins) @ vtable_flat       (one MXU pass)

where vtable_flat (F*Bp, Mp) is the lane-padded flattened table built by
core.artifact.finalize_artifact — feature f owns rows [f*Bp, (f+1)*Bp), so
the blocked one-hot selects all F partial terms in a single systolic pass
instead of F small matmuls in a Python loop.

The epilogue (plane votes / argmax / argmin + confidence) is elementwise and
lives in kernels/ops.py. Integer payloads ride as exact f32, so the result
is bit-identical to the integer-domain oracle sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.artifact import flatten_vtable
from repro.kernels.ensemble_lookup import _blocked_one_hot, _range_match
from repro.kernels.tuning import (DEFAULT_TILES, EXACT_F32,
                                  resolve_interpret)

TILE_N = DEFAULT_TILES.tile_n
EDGE_CHUNK = DEFAULT_TILES.edge_chunk


def _fused_classical_kernel(x_ref, edges_ref, vtab_ref, out_ref, *,
                            u_total: int, edge_chunk: int):
    x = x_ref[...]                                          # (TN, F)
    tn, f = x.shape
    b_pad = vtab_ref.shape[0] // f

    bins = _range_match(x, edges_ref, u_total, edge_chunk)
    oh = _blocked_one_hot(bins, b_pad)                      # (TN, F*Bp)
    out_ref[...] = jax.lax.dot(oh, vtab_ref[...],
                               preferred_element_type=jnp.float32,
                               precision=EXACT_F32)


def classical_lookup_fused(x, edges, vtable_flat, *, interpret=None,
                           tile_n=None, edge_chunk=None) -> jax.Array:
    """Single-matmul pipeline on the pre-flattened table.

    x (N, F) f32 with N % tile_n == 0; edges (F, U); vtable_flat (F*Bp, Mp)
    f32 -> (N, Mp) f32 sums (padded cols are zero; callers slice to M).
    """
    interpret = resolve_interpret(interpret)
    tile_n = tile_n or TILE_N
    edge_chunk = edge_chunk or EDGE_CHUNK
    n, f = x.shape
    u = edges.shape[1]
    fb, m_pad = vtable_flat.shape
    assert n % tile_n == 0, (n, tile_n)
    kernel = functools.partial(_fused_classical_kernel, u_total=u,
                               edge_chunk=edge_chunk)
    return pl.pallas_call(
        kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, f), lambda i: (i, 0)),
            pl.BlockSpec((f, u), lambda i: (0, 0)),
            pl.BlockSpec((fb, m_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, m_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m_pad), jnp.float32),
        interpret=interpret,
    )(x, edges, vtable_flat)


def classical_lookup_pallas(x, edges, vtable, *, interpret=None,
                            tile_n=None, edge_chunk=None) -> jax.Array:
    """x (N, F) f32, edges (F, U), vtable (F, U+1, M) -> (N, M) f32 sums.

    Compat entry: flattens vtable on the fly (serving uses the artifact's
    pre-flattened copy). interpret=None auto-detects the backend.
    """
    m = vtable.shape[2]
    out = classical_lookup_fused(x, edges, flatten_vtable(vtable),
                                 interpret=interpret, tile_n=tile_n,
                                 edge_chunk=edge_chunk)
    return out[:, :m]
