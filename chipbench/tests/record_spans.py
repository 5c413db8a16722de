#!/usr/bin/env python3
"""Record the fixture of ``test_chipbench_spans.py`` on the chip: a traced
stretch of ``jane_fin.b2048``, reduced to its last three calls, and the
step's scope map.

    python3 chipbench/tests/record_spans.py --seed <n> --calls <k> \\
        [--out <dir>]

Sets the cell up as ``run.py`` does, then serves ``--calls`` calls of its
traffic with the profiler on. With ``--out`` it writes
``trace_fin_spans_v5e.json.gz``, the trace reduced to the chip's programs
and ops and the host's Python thread lines over the last three calls, and
``trace_fin_spans_v5e.scopes.json.gz``, the scope map
(``HybridServer.step_scopes``). The last line of standard output is a
JSON summary of the stretch: where each call's step lies against its
spans (``spans.step_order``: how many calls break the order, and where),
and the device idle per step-to-step cycle against the mean ``hybrid.h2d``
span, the mean part of ``hybrid.dispatch`` before the step starts and the
idle outside the spans. These are the recorder's readings, not the
benchmark's. Without a TPU it exits 1.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT / "src"), str(ROOT))
                if p not in sys.path]

from chipbench import cells, spans, trace  # noqa: E402

FIXTURE = "trace_fin_spans_v5e"
FIXTURE_CALLS = 3
HOST_LINE = "python3"


def reduce_trace(data: dict, keep: int = FIXTURE_CALLS) -> dict:
    """The chip's ``XLA Modules`` and ``XLA Ops`` lines (ops without their
    stats) and the host's Python thread lines, from the ``hybrid.h2d``
    span of the ``keep``-th last call on."""
    t0 = sorted(e[1] for p in data["planes"] if p["name"] == "/host:CPU"
                for ln in p["lines"] if ln["name"] == HOST_LINE
                for e in ln["events"] if e[0] == spans.H2D)[-keep]
    planes = []
    for p in data["planes"]:
        if p["name"] == "/device:TPU:0":
            keep = (trace.MODULES_LINE, trace.OPS_LINE)
        elif p["name"] == "/host:CPU":
            keep = (HOST_LINE,)
        else:
            continue
        lines = [{"name": ln["name"],
                  "events": [e if p["name"] == "/host:CPU"
                             else [e[0], e[1], e[2], {}]
                             for e in ln["events"] if e[1] + e[2] >= t0]}
                 for ln in p["lines"] if ln["name"] in keep]
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def order_summary(order: list) -> dict:
    """Calls whose step starts before its dispatch span opens, or ends
    after the next call opens, and the spread of both margins (us)."""
    lead = [a * 1e-3 for a, _ in order]
    tail = [b * 1e-3 for _, b in order if b is not None]
    bad = [i for i, (a, b) in enumerate(order)
           if a < 0 or (b is not None and b < 0)]

    def spread(v):
        v = sorted(v)
        return [v[0], v[len(v) // 2], v[-1]] if v else None

    return {"calls": len(order), "out_of_order": len(bad),
            "out_of_order_calls": bad[:20], "lead_us": spread(lead),
            "tail_us": spread(tail)}


def record(spec: dict, seed: int, n_calls: int, log=print) -> tuple:
    """-> (extracted trace, scope map, trace keys) of ``n_calls`` traced
    calls of the cell."""
    import jax
    import numpy as np

    system = cells.system(spec["config"])
    cell = system.CELL(spec, seed, log)
    server, calls = cell.server, cell.calls(cell.traffic, len(cell.pool),
                                            seed)
    batches = [next(calls)[1] for _ in range(n_calls)]
    tdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for idx in batches:
        np.asarray(server.classify(cell.pool[idx])[0])
    jax.profiler.stop_trace()
    data = trace.extract(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    return data, server.step_scopes(len(batches[0])), system.TRACE_KEYS


def summarize(data: dict, step: str) -> dict:
    """Where the steps lie against the spans, and the idle split per
    step-to-step cycle, of a recorded stretch."""
    t = trace.DeviceTrace(data)
    order = spans.step_order(t, step)
    split = spans.idle_split(t, step)
    per = {k: v * 1e-3 / split["cycles"] for k, v in split.items()
           if k != "cycles"}
    out = {"order": order and order_summary(order),
           "h2d_us": spans.mean_us(t, spans.H2D),
           "dispatch_us": spans.mean_us(t, spans.DISPATCH),
           "idle_us_per_cycle": per, "cycles": split["cycles"]}
    if order:
        dispatch = sorted(spans.intervals(t, spans.DISPATCH))
        pre = sum(min(max(a, 0), e - s) for (a, _), (s, e)
                  in zip(order, dispatch)) * 1e-3 / len(order)
        out["dispatch_pre_device_us"] = pre
        out["h2d+pre+outside_over_idle"] = (
            out["h2d_us"] + pre + per["outside"]) / per["idle"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = cells.resolve(cells.load_benchmark(ROOT), "jane_fin.b2048", ROOT)
    if not cells.chip_ready("record_spans.py", 1):
        return 1
    data, scopes, keys = record(spec, args.seed % (1 << 63), args.calls)
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        with gzip.open(out / f"{FIXTURE}.json.gz", "wt") as f:
            json.dump(reduce_trace(data), f)
        with gzip.open(out / f"{FIXTURE}.scopes.json.gz", "wt") as f:
            json.dump(scopes, f, sort_keys=True)
    print(json.dumps(summarize(data, keys["step"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
