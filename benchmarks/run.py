"""Run every paper-table benchmark: ``python -m benchmarks.run``.

One module per paper table/figure (see DESIGN.md §12). Pass --quick for
reduced sample sizes (CI), --only <name> for a single benchmark.

Besides the printed tables, the suite writes machine-readable
``BENCH_benchmarks.json`` (schema "bench-v1", see DESIGN.md §11): one row
per benchmark with its wall time and whatever its run() returned, so the
perf trajectory of the repo is tracked run over run. The other bench-v1
emitters — ``kernel_microbench`` (BENCH_kernels.json), ``stream_bench``
(BENCH_stream.json), ``shard_stream_bench`` (BENCH_shard.json),
``batch_bench`` (BENCH_batch.json), ``scenario_bench``
(BENCH_scenarios.json) and ``analysis_bench`` (BENCH_analysis.json,
the device resource-fit trajectory) — are separate entry points with
their own gating oracles; ``--all-suites`` runs them here too, in this
process, so one command refreshes the whole trajectory. A failing
sub-suite fails the whole run immediately (its exit code is
propagated), so a broken oracle can never leave CI green.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from benchmarks.common import write_bench_json

BENCHES = [
    ("resource_anomaly", "Table 1"),
    ("resource_finance", "Table 2"),
    ("scalability", "Table 3"),
    ("feature_scaling", "Figs 4-5"),
    ("baseline_comparison", "Figs 6-7"),
    ("throughput_latency", "Fig 8"),
    ("calc_error", "Fig 9"),
    ("confidence_sweep", "Figs 10-11"),
    ("update_time", "§7.9"),
]

# the standalone bench-v1 emitters --all-suites chains after the in-process
# benches, each through its own main(argv) in this process
EXTRA_SUITES = ("kernel_microbench", "stream_bench", "shard_stream_bench",
                "batch_bench", "scenario_bench", "latency_bench",
                "obs_bench", "analysis_bench")


def run_suites(suite_modules, quick=False):
    """Run each standalone emitter in this process: ``benchmarks.<mod>
    .main(argv)``.

    One process, because on a chip host the process that first touches
    JAX holds the chip: a child started after the in-process benches
    could not reach it. A suite that needs several host devices on CPU
    gets them from the caller's ``XLA_FLAGS`` (CI's sharded step sets
    it); ``shard_stream_bench`` sizes its mesh sweep to the devices
    present. Exits the process on the FIRST failure — a raised exception
    (exit 1) or a nonzero ``SystemExit`` (its code) — so an oracle
    failure in one suite can never leave a caller that only checks "did
    it finish" green.
    """
    for mod_name in suite_modules:
        print(f"\n{'=' * 70}\nbenchmarks.{mod_name}\n{'=' * 70}",
              flush=True)
        rc = 0
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            mod.main(["--quick"] if quick else [])
        except SystemExit as e:
            rc = e.code
        except Exception:  # noqa: BLE001 — suite boundary: report and
            #                fail the whole run with the traceback shown
            traceback.print_exc()
            rc = 1
        if rc:
            print(f"benchmarks.{mod_name} FAILED (exit {rc})",
                  file=sys.stderr, flush=True)
            sys.exit(rc)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="BENCH_benchmarks.json",
                    help="machine-readable results file (bench-v1 schema)")
    ap.add_argument("--all-suites", action="store_true",
                    help="also run the kernel, streaming, sharded-"
                         "streaming, cross-window-batching and adversarial-"
                         "scenario benches (BENCH_kernels/stream/shard/"
                         "batch/scenarios.json); fails fast on the first "
                         "failing suite")
    args = ap.parse_args(argv)

    n = 6000 if args.quick else 20000
    t_all = time.time()
    failures = []
    results = []
    for mod_name, paper_ref in BENCHES:
        if args.only and args.only != mod_name:
            continue
        print(f"\n{'=' * 70}\n{paper_ref}  ->  benchmarks.{mod_name}"
              f"\n{'=' * 70}")
        t0 = time.time()
        entry = {"name": mod_name, "paper_ref": paper_ref, "ok": True,
                 "rows": None}
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            entry["rows"] = mod.run(n=n)
            print(f"[{mod_name}: {time.time() - t0:.1f}s]")
        except Exception:   # keep the suite going; report at the end
            traceback.print_exc()
            failures.append(mod_name)
            entry["ok"] = False
        entry["wall_s"] = round(time.time() - t0, 3)
        results.append(entry)
    if args.only and not results:
        names = ", ".join(m for m, _ in BENCHES)
        sys.exit(f"unknown benchmark {args.only!r}; choices: {names}")
    if args.out:
        write_bench_json(args.out, "benchmarks", results,
                         config={"n": n, "quick": args.quick,
                                 "only": args.only})
    if failures:
        # fail before launching sub-suites: a broken in-process bench
        # should not be buried under another suite's output
        print(f"\ntotal: {time.time() - t_all:.1f}s; "
              f"{len(failures)} failures {failures}")
        sys.exit(1)
    if args.all_suites:
        # run_suites exits nonzero on the first failing suite
        run_suites(EXTRA_SUITES, quick=args.quick)
    print(f"\ntotal: {time.time() - t_all:.1f}s; 0 failures")
    sys.exit(0)


if __name__ == "__main__":
    main()
