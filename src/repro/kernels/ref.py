"""Pure-jnp oracles for every kernel — the ground truth for allclose tests.

These share semantics with repro.core.inference (the reference data plane)
but expose the exact kernel contracts (same inputs, same outputs) so tests
sweep shapes/dtypes against them directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bucketize_ref(x: jax.Array, edges: jax.Array) -> jax.Array:
    """x (N, F), edges (F, U) (+inf padded) -> (N, F) int32 bin ids."""
    return jnp.sum(x[:, :, None] > edges[None, :, :], axis=2).astype(jnp.int32)


def ensemble_lookup_ref(x, edges, ftable, strides, dtable, *,
                        n_classes: int, vote: bool) -> jax.Array:
    """Gather-based oracle for the fused tree pipeline."""
    bins = bucketize_ref(x, edges)                          # (N, F)
    f_idx = jnp.arange(x.shape[1])[None, :]
    codes = ftable[f_idx, bins]                             # (N, F, T)
    keys = jnp.einsum("nft,tf->nt", codes.astype(jnp.int32),
                      strides).astype(jnp.int32)
    t_idx = jnp.arange(dtable.shape[0])[None, :]
    leaf = dtable[t_idx, keys]                              # (N, T)
    if vote:
        return jax.nn.one_hot(leaf.astype(jnp.int32), n_classes,
                              dtype=jnp.float32).sum(axis=1)
    return leaf.astype(jnp.float32).sum(axis=1, keepdims=True)


def classical_lookup_ref(x, edges, vtable) -> jax.Array:
    """Gather-based oracle for the classical pipeline. -> (N, M) f32."""
    bins = bucketize_ref(x, edges)
    f_idx = jnp.arange(x.shape[1])[None, :]
    vals = vtable[f_idx, bins]                              # (N, F, M)
    return vals.astype(jnp.float32).sum(axis=1)


def stream_update_ref(regs, bucket, ts, length, is_fwd, valid, *,
                      limit=None):
    """Oracle for the fused streaming scatter/readout kernel.

    regs (8, N) f32 — the stacked register file in
    ``netsim.stream.REGISTER_FIELDS`` order (pkt_count, byte_count,
    t_min, t_max, fwd_pkts, rev_pkts, fwd_bytes, rev_bytes); window
    columns (W,). Returns (new_regs (8, N), rows (8, W)): the register
    file with this window folded in (count registers clamped at
    ``limit`` when given — the 2^24 overflow guard) and the updated
    register rows gathered at each lane's bucket.

    Mirrors ``netsim.stream.update_flow_table`` + ``saturate_counts``
    op for op (same masked segment primitives, same identity pinning,
    same clamp) so the composition is bit-identical — the layering keeps
    this module free of netsim imports, so the mirroring is asserted by
    tests rather than shared code.
    """
    n = regs.shape[1]
    v = valid.astype(jnp.float32)
    ln, fwd = length, is_fwd
    seg = lambda x: jax.ops.segment_sum(x, bucket, num_segments=n)
    inf = jnp.float32(jnp.inf)
    w_min = jax.ops.segment_min(jnp.where(valid, ts, inf), bucket,
                                num_segments=n)
    w_max = jax.ops.segment_max(jnp.where(valid, ts, -inf), bucket,
                                num_segments=n)
    new = [regs[0] + seg(v),
           regs[1] + seg(ln * v),
           jnp.minimum(regs[2], w_min),
           jnp.maximum(regs[3], w_max),
           regs[4] + seg(fwd * v),
           regs[5] + seg((1.0 - fwd) * v),
           regs[6] + seg(ln * fwd * v),
           regs[7] + seg(ln * (1.0 - fwd) * v)]
    if limit is not None:
        lim = jnp.float32(limit)
        for i in (0, 1, 4, 5, 6, 7):              # count registers only
            new[i] = jnp.minimum(new[i], lim)
    new_regs = jnp.stack(new)
    return new_regs, new_regs[:, bucket]

