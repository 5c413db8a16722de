"""Pallas TPU kernel: fused streaming register scatter + touched-row gather.

The streaming serving step's register half is scatter-then-gather over
the same flow table: fold the window's packets into the per-bucket
registers (segment sums / min / max), clamp the count registers at the
2^24 f32 integer-exactness envelope, then gather each lane's updated
register row for the classify stage. Composed from XLA ops that is two
HBM round-trips over the (8, N) register file with a (W, 8) gather
materialized in between; here the whole pass is fused in VMEM per
bucket tile — the per-packet ALU + register read of the switch pipeline
as one kernel.

TPU realization (no native scatter on the VPU):

  scatter  -> a one-hot contraction. The (W, TILE_B) bucket-match
              one-hot against the six masked per-lane value vectors is
              ONE (6, W) x (W, TILE_B) MXU pass producing every count
              register's tile contribution; first/last-seen timestamps
              ride masked min/max reductions of the same match (VPU).
  gather   -> a masked-max over the same one-hot: exactly one tile
              matches each lane, so accumulating
              max(where(match, reg, -inf)) across grid steps
              reconstructs reg[bucket[w]] exactly — including the ±inf
              min/max identities of untouched buckets, which a
              multiply-gather would NaN-poison (inf * 0).

Exactness: count/byte registers are integer-valued f32 (exact below
2^24 in any association order), timestamps ride min/max (associative) —
so the matmul-scatter and masked-max gather are bit-identical to the
``kernels.ref.stream_update_ref`` segment-op oracle, asserted by
interpret-mode parity tests.

The register file is small ((8, N) f32: 256 KB at N=8192) but the match
one-hot is not — the bucket axis is tiled (grid over ``TILE_B`` column
blocks) so the (W, TILE_B) one-hot and its temporaries stay a few MB.
The rows output block is revisited by every grid step (TPU grids are
sequential) and initialized at step 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tuning import EXACT_F32, resolve_interpret

TILE_B = 512

N_REGISTERS = 8
# indices into the stacked register file (netsim.stream.REGISTER_FIELDS
# order); the module is deliberately free of netsim imports — layering —
# so the order is pinned here and asserted by tests
IDX_COUNTS = (0, 1, 4, 5, 6, 7)        # pkt, byte, fwd/rev pkts, fwd/rev bytes
IDX_T_MIN = 2
IDX_T_MAX = 3


def _stream_update_kernel(bucket_ref, ts_ref, len_ref, fwd_ref, valid_ref,
                          regs_ref, out_regs_ref, rows_ref, *,
                          tile_b: int, limit):
    j = pl.program_id(0)
    b = bucket_ref[0, :]                               # (W,) i32
    ts = ts_ref[0, :]                                  # (W,) f32
    ln = len_ref[0, :]
    fw = fwd_ref[0, :]
    vf = (valid_ref[0, :] != 0).astype(jnp.float32)
    w = b.shape[0]

    iota = (jax.lax.broadcasted_iota(jnp.int32, (w, tile_b), 1)
            + j * tile_b)
    match = b[:, None] == iota                         # (W, TILE_B) one-hot
    matchv = match & (vf[:, None] > 0.0)               # pad lanes masked out

    # scatter: all six count-register contributions in ONE MXU pass
    vals = jnp.stack([vf, ln * vf, fw * vf, (1.0 - fw) * vf,
                      ln * fw * vf, ln * (1.0 - fw) * vf])       # (6, W)
    contrib = jax.lax.dot_general(
        vals, matchv.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=EXACT_F32)                                # (6, TILE_B)

    old = regs_ref[...]                                # (8, TILE_B)
    inf = jnp.float32(jnp.inf)
    t_min = jnp.minimum(old[IDX_T_MIN],
                        jnp.min(jnp.where(matchv, ts[:, None], inf), axis=0))
    t_max = jnp.maximum(old[IDX_T_MAX],
                        jnp.max(jnp.where(matchv, ts[:, None], -inf), axis=0))
    counts = [old[i] + contrib[k] for k, i in enumerate(IDX_COUNTS)]
    if limit is not None:
        counts = [jnp.minimum(c, jnp.float32(limit)) for c in counts]
    new = jnp.stack([counts[0], counts[1], t_min, t_max,
                     counts[2], counts[3], counts[4], counts[5]])

    out_regs_ref[...] = new

    # gather: masked-max select — exact even for ±inf identities
    @pl.when(j == 0)
    def _init():
        rows_ref[...] = jnp.full((N_REGISTERS, w), -inf, jnp.float32)

    gathered = jnp.stack([
        jnp.max(jnp.where(match, new[r][None, :], -inf), axis=1)
        for r in range(N_REGISTERS)])                  # (8, W)
    rows_ref[...] = jnp.maximum(rows_ref[...], gathered)


def stream_update_pallas(regs, bucket, ts, length, is_fwd, valid, *,
                         limit=None, interpret=None, tile_b=None):
    """regs (8, N) f32 stacked register file, window columns (W,)
    -> (new_regs (8, N), rows (8, W)).

    N must be a multiple of ``tile_b`` (ops.py pads; bucket ids are < N,
    so pad columns are never matched and pass through with only the
    clamp applied — sliced off by the wrapper). ``limit`` clamps the
    count registers (the 2^24 overflow guard) inside the same pass;
    None skips it bit-exactly. interpret=None auto-detects the backend.
    """
    interpret = resolve_interpret(interpret)
    tile_b = tile_b or TILE_B
    r, n = regs.shape
    assert r == N_REGISTERS, r
    assert n % tile_b == 0, (n, tile_b)
    w = bucket.shape[0]
    kernel = functools.partial(_stream_update_kernel, tile_b=tile_b,
                               limit=limit)
    row = lambda a, dt: a[None, :].astype(dt)
    return pl.pallas_call(
        kernel,
        grid=(n // tile_b,),
        in_specs=[
            pl.BlockSpec((1, w), lambda j: (0, 0)),
            pl.BlockSpec((1, w), lambda j: (0, 0)),
            pl.BlockSpec((1, w), lambda j: (0, 0)),
            pl.BlockSpec((1, w), lambda j: (0, 0)),
            pl.BlockSpec((1, w), lambda j: (0, 0)),
            pl.BlockSpec((r, tile_b), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((r, tile_b), lambda j: (0, j)),
            pl.BlockSpec((r, w), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, n), jnp.float32),
            jax.ShapeDtypeStruct((r, w), jnp.float32),
        ],
        interpret=interpret,
    )(row(bucket, jnp.int32), row(ts, jnp.float32),
      row(length, jnp.float32), row(is_fwd, jnp.float32),
      row(valid, jnp.int32), regs)
