"""Finding a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix. The configuration's file
is the one ``BENCHMARK.json`` lists for it, and names its ``system``:
``systems/<system>.py`` runs it and ``reference/<system>.py`` is the
plain reference it is checked against. The traffic mix is
``traffic/<traffic>.json``, whose ``kind`` names the source that turns
it into calls, ``sources/<kind>.py``. Each per-layer metric is read by
``metrics/<name>.py``, or, for a name ``<quantity>.<suffix>`` with no
file of its own, by ``metrics/<quantity>.py``. So a new cell, mix,
configuration or metric is new files and entries, not edits.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str, root: Path, pkg: Path = HERE) -> dict:
    """Everything one cell needs: its entry, its configuration and traffic
    as loaded from their files (configurations by the path
    ``BENCHMARK.json`` gives under ``root``, mixes under ``pkg``), and the
    end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((pkg / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return dict(workload=w, config=cfg, traffic=traffic,
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]), pkg=pkg)


def system(cfg: dict):
    """The module that runs a configuration's ``system``."""
    return importlib.import_module(f"chipbench.systems.{cfg['system']}")


def _load(path: Path):
    name = "chipbench_" + "_".join(path.relative_to(path.parents[1])
                                   .with_suffix("").parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def source(kind: str, pkg: Path = HERE):
    """The ``calls`` generator of traffic kind ``kind``."""
    path = pkg / "sources" / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"no traffic source {kind!r} ({path.name} is not in "
                       f"{path.parent})")
    return _load(path).calls


def reader(name: str, pkg: Path = HERE):
    """The ``read`` function of per-layer metric ``name``."""
    path = pkg / "metrics" / f"{name}.py"
    if not path.is_file():
        path = pkg / "metrics" / f"{name.split('.')[0]}.py"
    return _load(path).read


def chip_ready(tool: str, n_chips: int) -> bool:
    """Whether JAX finds a TPU with at least ``n_chips`` chips; if so, keep
    the persistent compile cache where the program puts it (the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, or ``.jax_cache`` in the
    checkout). Says why not on standard error."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache

    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"device_count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        print(f"{tool}: no TPU (JAX found {devs[0].platform}); nothing was "
              f"run", file=sys.stderr)
        return False
    if len(devs) < n_chips:
        print(f"{tool}: {n_chips} chips needed, JAX found {len(devs)}; "
              f"nothing was run", file=sys.stderr)
        return False
    print(f"compile_cache={configure_compile_cache() or 'off'}", flush=True)
    return True
