"""Hypothesis property tests on the system's invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed; property tests skipped")
from hypothesis import given, settings, strategies as st

from repro.core.quantize import dequantize, quantize_fixed
from repro.kernels import ref

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


_FLOATS = st.floats(-1e4, 1e4, allow_nan=False, width=32,
                    allow_subnormal=False)   # XLA flushes subnormals (FTZ)


@given(
    st.integers(1, 40).flatmap(lambda n: st.tuples(
        st.just(n),
        st.lists(_FLOATS, min_size=n, max_size=n),
    )),
    st.lists(_FLOATS, min_size=1, max_size=12),
)
def test_bucketize_is_rank(pair, edges_raw):
    """bucketize(x) == #{edges < ... } is the rank of x among edges —
    monotone in x, bounded by the edge count, exact on ties."""
    _, xs = pair
    edges = np.sort(np.asarray(edges_raw, np.float32))
    x = np.asarray(xs, np.float32)[:, None]                 # (N, 1)
    out = np.asarray(ref.bucketize_ref(
        jnp.asarray(x), jnp.asarray(edges[None, :])))[:, 0]
    # bounds
    assert out.min() >= 0 and out.max() <= len(edges)
    # monotone: sort x, bins must be sorted
    order = np.argsort(x[:, 0], kind="stable")
    assert (np.diff(out[order]) >= 0).all()
    # exact semantics
    expect = (x > edges[None, :]).sum(axis=1)
    np.testing.assert_array_equal(out, expect)


@given(st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                min_size=1, max_size=200),
       st.sampled_from([8, 12, 16, 24]))
def test_quantize_bounded_error(vals, bits):
    """|dequant(quant(v)) - v| <= max|v| / (2^(bits-1) - 1) elementwise."""
    v = np.asarray(vals, np.float32)
    fp = quantize_fixed(v, bits)
    deq = np.asarray(dequantize(fp))
    bound = (np.abs(v).max() + 1e-12) / (2 ** (bits - 1) - 1)
    assert np.all(np.abs(deq - v) <= bound * 1.0001)


@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32,
                          allow_subnormal=False), min_size=1, max_size=200),
       st.sampled_from([4, 8, 12, 16]))
def test_quantize_symmetric_range(vals, bits):
    """Symmetric fixed point never dequantizes past max|v|: the clip is
    ±qmax, not [-qmax-1, qmax] (regression — the extra negative code
    broke the module's symmetric contract)."""
    v = np.asarray(vals, np.float32)
    fp = quantize_fixed(v, bits)
    qmax = 2 ** (bits - 1) - 1
    assert int(np.asarray(fp.q).min()) >= -qmax
    assert int(np.asarray(fp.q).max()) <= qmax
    max_abs = max(float(np.abs(v).max()), 1e-12)
    assert float(np.abs(np.asarray(dequantize(fp))).max()) \
        <= max_abs * (1 + 1e-6)


@given(st.integers(2, 64), st.integers(1, 8), st.integers(0, 3))
def test_quantize_integer_sum_exact(n, m, seed):
    """Summing in the integer domain then dequantizing == summing
    dequantized values (the switch-ALU exactness property)."""
    rng = np.random.default_rng(seed)
    v = rng.normal(0, 10, (n, m)).astype(np.float32)
    fp = quantize_fixed(v, 16)
    left = fp.q.sum(axis=0).astype(np.float32) / np.asarray(fp.scale)
    right = np.asarray(dequantize(fp)).sum(axis=0)
    # f32 rounding in the two division orders; integer path is the exact one
    np.testing.assert_allclose(left, right, rtol=1e-4, atol=1e-4)


@given(st.integers(1, 30), st.integers(2, 8), st.integers(0, 5))
def test_hybrid_dispatch_roundtrip(n_fwd, cap, seed):
    """dispatch/combine: forwarded rows (up to capacity) get the backend
    answer; everything else keeps the switch answer."""
    from repro.core.hybrid import combine, dispatch
    rng = np.random.default_rng(seed)
    n = 32
    mask = np.zeros(n, bool)
    mask[rng.choice(n, size=min(n_fwd, n), replace=False)] = True
    x = rng.normal(0, 1, (n, 3)).astype(np.float32)
    sw = np.zeros(n, np.int32)
    buf, idx, valid = dispatch(jnp.asarray(x), jnp.asarray(mask), cap)
    be = jnp.ones((cap,), jnp.int32)
    out = np.asarray(combine(jnp.asarray(sw), be, idx, valid))
    n_served = min(int(mask.sum()), cap)
    assert out.sum() == n_served
    # all served rows were actually forwarded rows
    assert np.all(mask[np.asarray(idx)[np.asarray(valid)]])


_INGEST_TRACE = None


def _ingest_trace():
    """Small shared trace for the ring-replay property (built lazily so
    collection stays import-cheap when hypothesis is absent)."""
    global _INGEST_TRACE
    if _INGEST_TRACE is None:
        from repro.netsim.packets import synth_trace
        _INGEST_TRACE = synth_trace(n_flows=30, seed=17)
    return _INGEST_TRACE


@given(st.integers(1, 400), st.sampled_from([3, 5, 8]),
       st.sampled_from([1, 2, 3]), st.booleans())
def test_ring_replay_bit_identical_to_iter_chunks(batch, window, k,
                                                  use_deadline):
    """Window-granular cut invariant (DESIGN.md §13): replaying a trace
    through the ingest ring in ANY batch size, with count cuts, deadline
    cuts (aggressive fake clock) and the ragged-tail drain all firing,
    yields exactly the window sequence of the offline ``iter_chunks``
    iterator — cuts regroup windows, they never move a boundary."""
    from repro.netsim.ingest import PacketRingBuffer, cut_stream, \
        replay_source
    from repro.netsim.stream import iter_chunks
    trace = _ingest_trace()
    n_buckets = 64
    state = {"t": 0.0}

    def clock():
        state["t"] += 1.0          # every look at the clock ages the ring
        return state["t"]

    ring = PacketRingBuffer(window, k, n_buckets,
                            deadline=0.5 if use_deadline else None,
                            clock=clock)
    cuts = list(cut_stream(ring, replay_source(trace, batch=batch)))
    assert sum(c.n for c in cuts) == trace.n_packets
    assert ring.stats.admitted == trace.n_packets
    assert ring.stats.dropped == 0             # pull-based: nothing drops
    assert all(c.kind in ("count", "deadline", "drain") for c in cuts)
    if use_deadline:
        assert ring.stats.deadline_cuts + ring.stats.count_cuts \
            + ring.stats.drain_cuts == len(cuts)
    else:
        assert ring.stats.deadline_cuts == 0

    ref = list(iter_chunks(trace, window, k, n_buckets))
    n_live = -(-trace.n_packets // window) * window   # live windows, padded
    for field in ("bucket", "ts", "length", "is_fwd", "valid"):
        got = np.concatenate([
            (c.valid if field == "valid" else c.cols[field])
            [:c.n_windows * c.window] for c in cuts])
        want = np.concatenate([np.asarray(getattr(rc, field)).reshape(-1)
                               for rc in ref])[:n_live]
        np.testing.assert_array_equal(got, want, err_msg=field)
