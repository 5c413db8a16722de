#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (inputs and models from the seed, the server, one warm-up pass of
the cell's own traffic) is timed as ``setup_s`` from the start of this
script. The window then runs for ``--seconds`` with the profiler off
(``--trace 0``: the cell's end-to-end metrics) or on (``--trace 1``: its
per-layer metrics, with the device's busy time and a breakdown). After
the window the served outputs are compared with the plain reference; each
number compared is printed beside its limit on the last lines of standard
error and under ``checks`` in the result, and ``correct`` says whether
all held. The last line of standard output is the result as one JSON
object. Without a TPU, or with fewer chips than the cell needs, nothing
runs and the exit code is 1.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(ROOT / "src"), str(ROOT))
                if p not in sys.path]

from chipbench import cells  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts programs lowered for compilation (each jit cache miss,
    whether or not the persistent cache then holds the executable)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == self.EVENT:
            self.count += 1


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:n_chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Set up, serve the window, check, and reduce. -> the result dict.
    Runs on whatever devices JAX has: ``main`` looks for the chip."""
    import jax

    compiles = CompileCounter()
    cfg = spec["config"]
    cell = cells.system(cfg).CELL(spec, seed, log)
    setup_s = time.time() - T_START
    log(f"[setup] setup_s={setup_s} compiles_in_setup={compiles.count}")

    compiles.count = 0
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.monotonic()
    out = cell.serve(seconds)
    window_s = time.monotonic() - t0
    if trace:
        jax.profiler.stop_trace()
    n_compiles = compiles.count
    dev = device_info(spec["workload"]["chips"])
    log(f"[window] seconds={window_s} compiles_in_window={n_compiles} "
        f"peak_bytes_in_use={dev['memory_peak_bytes']}")
    res = cell.results(out)

    metrics = {}
    breakdown = None
    if trace:
        from chipbench import trace as tr
        data = tr.extract(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        dtr = tr.DeviceTrace(data, 0)
        busy_s = dtr.busy_ns() * 1e-9
        dev.update(busy_s=busy_s, window_s=window_s)
        rec = dict(res["record"], trace=dtr, window_s=window_s,
                   busy_s=busy_s)
        for m in spec["per_layer"]:
            v = cells.reader(m["name"], spec["pkg"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": dtr.top_ops(10),
                     "idle_gaps": dtr.idle_gaps(10)}
    else:
        for m in spec["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else res["e2e"][m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = cell.check(out)
    limits = cfg["limits"]
    if set(checks) != set(limits):
        raise RuntimeError(f"checks {sorted(checks)} do not match the "
                           f"limits {sorted(limits)}")
    correct = all(checks[k] <= limits[k] for k in limits)
    for k in limits:
        print(f"check {k}={checks[k]} limit={limits[k]}", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cells.resolve(cells.load_benchmark(ROOT), args.workload, ROOT)
    if not cells.chip_ready("run.py", spec["workload"]["chips"]):
        return 1
    result = run_cell(spec, args.seed % (1 << 63), args.seconds,
                      bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
