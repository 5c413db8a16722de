#!/usr/bin/env python3
"""The readings that a cell's limits are set from, for a dozen seeds.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

For each seed: build the cell, serve one window at the cell's own load,
then print one JSON line with the numbers compared for the program
against the float32 reference (the lower readings) and for the control
against the same reference (the upper readings). The control is the
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place. The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(ROOT / "src"), str(ROOT))
                if p not in sys.path]

from chipbench import cells  # noqa: E402


def readings(spec: dict, seed: int, seconds: float) -> dict:
    import ml_dtypes
    cell = cells.system(spec["config"]).CELL(spec, seed, lambda m: None)
    out = cell.serve(seconds)
    return {"seed": seed, "program": cell.check(out),
            "control": cell.control(out, ml_dtypes.bfloat16)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = cells.resolve(cells.load_benchmark(ROOT), args.workload, ROOT)
    if not cells.chip_ready("control.py", spec["workload"]["chips"]):
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
