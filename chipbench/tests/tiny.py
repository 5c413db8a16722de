"""Cells of the benchmark shrunk to a size the CPU tests can run."""

from __future__ import annotations

from pathlib import Path

from chipbench import cells

ROOT = Path(__file__).resolve().parents[2]


def tiny_spec(workload: str, root: Path = ROOT, pkg: Path = cells.HERE):
    """The cell as ``BENCHMARK.json`` defines it, at toy sizes: small row
    pools and training samples, 256-row calls, a backend capacity of
    128."""
    spec = cells.resolve(cells.load_benchmark(root), workload, root, pkg)
    cfg = spec["config"]
    cfg["pool_rows"] = 4000
    cfg["models"]["train_rows"] = 2000
    cfg["server"]["capacity"] = 128
    spec["traffic"]["batch"] = 256
    return spec
