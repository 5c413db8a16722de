"""The trace reduction, checked by hand on a small trace recorded on a
TPU v5e, and the per-layer readers on top of it."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from chipbench import cells, trace
from chipbench.systems.fin_batch import TRACE_KEYS

DATA = Path(__file__).resolve().parent / "data"


def load_data(name="trace_fin_v5e.json.gz"):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def load(name="trace_fin_v5e.json.gz"):
    return trace.DeviceTrace(load_data(name))


def test_union_and_parse():
    assert trace.union_ns([(0, 10), (5, 12), (20, 25), (21, 22)]) == 17
    assert trace.merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    op = ('%fused_classify.2 = f32[16384,2]{1,0} custom-call(f32[16384,8] '
          '%bitcast.191), custom_call_target="tpu_custom_call"')
    assert trace.parse_op(op) == ("fused_classify", "custom-call",
                                  "tpu_custom_call")
    assert trace.parse_op("%while.2 = (s32[]{:T(128)}, f32[8]) while("
                          "(s32[], f32[8]) %t), body=%b")[1] == "while"


def covered(spans) -> int:
    """Covered length by a sweep over start (+1) and end (-1) points."""
    pts = sorted([(s, 1) for s, _ in spans] + [(e, -1) for _, e in spans])
    depth, last, total = 0, None, 0
    for x, d in pts:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


def by_hand(data, module):
    """Busy time, the program's spans and its ops, by a plain loop."""
    dev = [p for p in data["planes"] if p["name"] == "/device:TPU:0"][0]
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    mods = [(e[1], e[1] + e[2]) for e in lines["XLA Modules"]
            if e[0].startswith(module)]
    busy = covered([(s, s + d) for _, s, d, _ in lines["XLA Ops"]])
    return busy, mods, lines["XLA Ops"]


def test_fin_reduction_matches_a_hand_count():
    data = load_data()
    t = trace.DeviceTrace(data)
    busy, mods, ops = by_hand(data, "jit_step(")
    assert t.busy_ns() == busy
    assert t.module_ns(TRACE_KEYS["step"]) == sum(e - s for s, e in mods)
    kernel = covered([(s, s + d) for name, s, d, _ in ops
                      if 'custom_call_target="tpu_custom_call"' in name
                      and any(a <= s < b for a, b in mods)])
    assert kernel > 0
    assert t.op_ns(**TRACE_KEYS["classify"]) == kernel


def test_fin_readers_and_breakdown():
    t = load()
    rec = dict(trace=t, rows=3 * 2048, calls=3, window_s=1.0,
               busy_s=t.busy_ns() * 1e-9, keys=TRACE_KEYS)
    fc = cells.reader("fused_classify_us_per_krow.fin")(rec)
    rest = cells.reader("step_rest_us_per_krow.fin")(rec)
    assert 0 < fc < rest                   # the backend forest dominates
    step = t.module_ns(TRACE_KEYS["step"])
    assert (fc + rest) * 6.144 == pytest.approx(step * 1e-3)
    top = t.top_ops(3)
    assert len(top) == 3 and all(s > 0 for _, s in top)
    assert all(g > 0 for _, g in t.idle_gaps(3))
    assert cells.reader("device_idle_pct.fin")(rec) == pytest.approx(
        100 * (1 - t.busy_ns() * 1e-9))


def test_a_reader_with_nothing_to_read_returns_none():
    empty = trace.DeviceTrace({"planes": [{"name": "/device:TPU:0",
                                           "lines": []}]})
    rec = dict(trace=empty, rows=1000, calls=1, keys=TRACE_KEYS)
    for name in ("fused_classify_us_per_krow.fin",
                 "step_rest_us_per_krow.fin"):
        assert cells.reader(name)(rec) is None, name
    assert cells.reader("device_idle_pct.fin")({"window_s": 0}) is None


def test_extract_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    data = trace.extract(str(tmp_path))
    names = [p["name"] for p in data["planes"]]
    assert "/host:CPU" in names
    with pytest.raises(ValueError):
        trace.DeviceTrace(data)            # no TPU plane in a CPU trace
