"""The readers of the program's host spans: by hand on a small made-up
trace, silent on a trace of a program without spans, and the span names
they read are the ones ``HybridServer.classify`` opens. Where each step
lies against its spans, on a v5e recording with the host and device
clocks in line and on one without. The backend's device counter against
the plain reference's count of sent rows."""

from __future__ import annotations

import contextlib
import gzip
import json
import re
from pathlib import Path

import numpy as np
import pytest

from chipbench import cells, spans, trace
from chipbench.reference import fin_batch as ref
from chipbench.systems.fin_batch import TRACE_KEYS
from chipbench.tests.tiny import tiny_spec

DATA = Path(__file__).resolve().parent / "data"
READERS = ("classify_h2d_us_per_call.fin",
           "classify_dispatch_us_per_call.fin")


def made_up(steps=(100, 400, 800)) -> trace.DeviceTrace:
    """Three steps on the chip, two calls' spans on the host between them
    (times in ns):

        ops       100-150 160-200 | 400-500 | 800-900   (steps at 100,
                                                          400 and 800)
        h2d       210-300         | 600-650
        dispatch  300-420         | 650-820
        other     200-390 (not a hybrid.* span)
    """
    op = "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop"
    ops = [[op, s, e - s, {}] for s, e in
           ((100, 150), (160, 200), (400, 500), (800, 900))]
    mods = [["jit_step(7)", s, 100, {}] for s in steps]
    host = [["np.asarray(jax.Array)", 200, 190, {}],
            ["hybrid.h2d", 210, 90, {"call": 1}],
            ["hybrid.dispatch", 300, 120, {"call": 1}],
            ["hybrid.h2d", 600, 50, {"call": 2}],
            ["hybrid.dispatch", 650, 170, {"call": 2}]]
    return trace.DeviceTrace({"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Modules", "events": mods},
                   {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": host}]}]})


def test_span_readers_by_hand():
    rec = dict(trace=made_up(), keys=TRACE_KEYS, rows=512, calls=2)
    # window 100-900: 800 long, 290 busy -> 510 idle; idle inside the
    # h2d spans 90 + 50, inside the dispatch spans 100 + 150, outside
    # 150-160, 200-210 and 500-600 -> 120, over 2 step-to-step cycles
    assert spans.idle_split(rec["trace"], TRACE_KEYS["step"]) == {
        "idle": 510, "h2d": 140, "dispatch": 250, "outside": 120,
        "cycles": 2}
    h2d, dispatch = (cells.reader(n)(rec) for n in READERS)
    assert h2d == pytest.approx((90 + 50) / 2 * 1e-3)
    assert dispatch == pytest.approx((120 + 170) / 2 * 1e-3)


def test_step_order_by_hand():
    """Each call's step against its spans: the step of call 1 runs
    400-500 (dispatch opens at 300, call 2's h2d at 600), that of call 2
    800-900 (dispatch at 650, no next call). A device clock 200 ns early
    puts call 1's step before its dispatch; a step without a span of its
    own (the one at 100) leaves nothing to pair."""
    step = TRACE_KEYS["step"]
    assert spans.step_order(made_up((400, 800)), step) == [(100, 100),
                                                           (150, None)]
    assert spans.step_order(made_up((200, 600)), step) == [(-100, 300),
                                                           (-50, None)]
    assert spans.step_order(made_up(), step) is None


def test_span_readers_are_silent_without_spans():
    """The trace of a program that opens no spans (the recorded v5e trace
    of the cell before the spans existed) gives no reading, and raises
    nothing."""
    with gzip.open(DATA / "trace_fin_v5e.json.gz", "rt") as f:
        t = trace.DeviceTrace(json.load(f))
    rec = dict(trace=t, keys=TRACE_KEYS, rows=3 * 2048, calls=3,
               window_s=1.0, busy_s=t.busy_ns() * 1e-9)
    for name in READERS:
        assert cells.reader(name)(rec) is None, name


def test_the_readers_read_the_spans_classify_opens(monkeypatch):
    import repro.serving.hybrid_serving as hs
    opened = []

    @contextlib.contextmanager
    def span(name, **ids):
        opened.append(name)
        yield

    monkeypatch.setattr(hs, "span", span)
    spec = tiny_spec("jane_fin.b2048")
    cell = cells.system(spec["config"]).CELL(spec, 3, lambda m: None)
    assert opened == [spans.H2D, spans.DISPATCH]     # the warm-up call
    assert all(n.startswith(spans.PREFIX) for n in opened)
    assert cell.server.calls == 1


@pytest.mark.parametrize("seed", [2**33 + 1, 5])
def test_backend_rows_match_the_reference_call_for_call(seed):
    """``HybridStats.backend_rows``, the dispatch layer's counter, equals
    the number of rows the plain reference sends to the backend, call for
    call, so the buffer's fill reads the same from either."""
    spec = tiny_spec("jane_fin.b2048")
    cell = cells.system(spec["config"]).CELL(spec, seed, lambda m: None)
    cfg = spec["config"]["server"]
    ans = ref.pool_answers(cell.pool, cell.switch, cell.backend)
    calls = cell.calls(cell.traffic, len(cell.pool), seed)
    got, want = [], []
    for _ in range(4):
        _, idx = next(calls)
        _, st = cell.server.classify(cell.pool[idx])
        got.append(st.backend_rows)
        # the reference's rule (reference/fin_batch.call_answer's ``sent``)
        fwd = ans["conf"][idx] < np.float32(cfg["threshold"])
        want.append(int((fwd & (np.cumsum(fwd) <= cfg["capacity"])).sum()))
    assert got == want and sum(want) > 0
    assert st.capacity == cfg["capacity"]


def load_fixture():
    with gzip.open(DATA / "trace_fin_spans_v5e.json.gz", "rt") as f:
        data = json.load(f)
    with gzip.open(DATA / "trace_fin_spans_v5e.scopes.json.gz", "rt") as f:
        scopes = json.load(f)
    return data, scopes


def lines_of(data):
    dev = [p for p in data["planes"] if p["name"] == "/device:TPU:0"][0]
    host = [p for p in data["planes"] if p["name"] == "/host:CPU"][0]
    out = {ln["name"]: ln["events"] for ln in dev["lines"]}
    out["host"] = [e for ln in host["lines"] for e in ln["events"]]
    return out


def outside_by_sweep(data) -> float:
    """Idle time with no hybrid.* span open, between the first and the
    last jit_step, per step-to-step cycle (us): a plain sweep over every
    elementary segment between two event boundaries."""
    ln = lines_of(data)
    steps = [(s, s + d) for n, s, d, _ in ln["XLA Modules"]
             if n.startswith("jit_step(")]
    lo, hi = steps[0][0], steps[-1][1]
    ops = [(s, s + d) for _, s, d, _ in ln["XLA Ops"]]
    host = [(s, s + d) for n, s, d, _ in ln["host"]
            if n.startswith("hybrid.")]
    pts = sorted({lo, hi} | {x for iv in ops + host for x in iv
                             if lo < x < hi})
    outside = 0
    for a, b in zip(pts, pts[1:]):
        m = (a + b) / 2
        if not any(s <= m < e for s, e in ops + host):
            outside += b - a
    return outside * 1e-3 / (len(steps) - 1)


def scope_ns(data, scopes, step) -> dict:
    """Device time of the ops inside runs of the program matching the regex
    ``step``, by the scope their full instruction name maps to."""
    ln = lines_of(data)
    rx = re.compile(step)
    runs = trace.merged((s, s + d) for n, s, d, _ in ln["XLA Modules"]
                        if rx.search(n))
    by: dict = {}
    for name, s, d, _ in ln["XLA Ops"]:
        if any(a <= s < b for a, b in runs):
            full = name[1:name.index(" = ")]
            by.setdefault(scopes.get(full, "none"), []).append((s, s + d))
    return {k: trace.union_ns(v) for k, v in by.items()}


def test_the_recorded_steps_run_inside_their_calls():
    """The fixture was recorded with the host and device clocks in line:
    each step starts after its call's dispatch span opens and ends before
    the caller opens its next call, which the idle split relies on."""
    data, _ = load_fixture()
    order = spans.step_order(trace.DeviceTrace(data), TRACE_KEYS["step"])
    assert order is not None and len(order) == 3
    assert all(lead >= 0 for lead, _ in order)
    assert all(tail >= 0 for _, tail in order[:-1])


def test_step_order_finds_a_device_clock_ahead_of_the_host():
    """Three calls recorded the same way by the first process on a fresh
    v5e machine: every device event of the trace sits 0.3-1.0 ms early
    against the host's, so each step seems to start before the host
    dispatched it, and the idle split would put idle inside h2d that the
    host spent before the step. ``step_order`` shows it."""
    with gzip.open(DATA / "trace_fin_spans_v5e_skewed.json.gz", "rt") as f:
        t = trace.DeviceTrace(json.load(f))
    order = spans.step_order(t, TRACE_KEYS["step"])
    assert order is not None and len(order) == 3
    assert all(-1_000_000 < lead < -300_000 for lead, _ in order)


def test_span_readers_on_a_v5e_trace():
    """Three calls of the cell recorded on a TPU v5e: the readers against
    the same quantities found by plain loops over the recorded events."""
    data, _ = load_fixture()
    t = trace.DeviceTrace(data)
    rec = dict(trace=t, keys=TRACE_KEYS, rows=3 * 2048, calls=3)
    ln = lines_of(data)
    for reader, name in zip(READERS, (spans.H2D, spans.DISPATCH)):
        durs = [d for n, _, d, _ in ln["host"] if n == name]
        assert len(durs) == 3
        assert cells.reader(reader)(rec) == pytest.approx(
            sum(durs) / 3 * 1e-3)
    split = spans.idle_split(t, TRACE_KEYS["step"])
    assert split["outside"] * 1e-3 / split["cycles"] == pytest.approx(
        outside_by_sweep(data))


def test_the_spans_of_a_call_share_its_id():
    data, _ = load_fixture()
    ids = [(n, st["call"]) for n, _, _, st in lines_of(data)["host"]
           if n.startswith(spans.PREFIX)]
    first = ids[0][1]
    assert ids == [(n, first + i) for i in range(3)
                   for n in (spans.H2D, spans.DISPATCH)]


def test_the_scope_map_names_the_ops_of_the_recorded_step():
    """The step's scope map (``HybridServer.step_scopes`` on the chip)
    keys instructions as the trace's op events name them: the classify
    kernel maps to ``switch``, and the ``backend`` scope holds 90-100% of
    the step's device time outside the kernel."""
    data, scopes = load_fixture()
    t = trace.DeviceTrace(data)
    kernel = [n for n, _, _, _ in lines_of(data)["XLA Ops"]
              if 'custom_call_target="tpu_custom_call"' in n]
    assert kernel and all(scopes[k[1:k.index(" ")]] == "switch"
                          for k in kernel)
    by = scope_ns(data, scopes, TRACE_KEYS["step"])
    classify = t.op_ns(**TRACE_KEYS["classify"])
    rest = t.module_ns(TRACE_KEYS["step"]) - classify
    assert 0.9 <= by["backend"] / rest <= 1.0
    assert by["switch"] >= classify        # the kernel and its argmax
