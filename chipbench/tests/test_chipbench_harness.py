"""The harness finds every part by name, adds cells by data alone, prints
the contract's result line, and refuses to run without a TPU."""

from __future__ import annotations

import json
import shutil

import pytest

from chipbench import cells, run
from chipbench.tests.tiny import ROOT, tiny_spec

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def test_every_cell_resolves_to_files_of_its_own():
    bench = cells.load_benchmark(ROOT)
    for w in bench["workloads"]:
        spec = cells.resolve(bench, w["name"], ROOT)
        assert cells.system(spec["config"]).CELL is not None
        assert callable(cells.source(spec["traffic"]["kind"]))
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        assert set(spec["config"]["limits"])
    for m in bench["per_layer"]:
        assert callable(cells.reader(m["name"]))


PACED = '''
import numpy as np


def calls(traffic, n_pool, seed):
    rng = np.random.default_rng([seed, 5])
    batch, gap = traffic["batch"], 1.0 / traffic["calls_per_s"]
    i = 0
    while True:
        yield i * gap, rng.integers(0, n_pool, batch)
        i += 1
'''


def test_a_new_cell_needs_only_new_files_and_an_entry(tmp_path):
    """A copy of the benchmark gains a traffic kind, a mix, a metric
    reader and a cell by new files and new entries, and a per-layer
    quantity for the new cell by an entry alone; nothing that exists is
    edited."""
    root = tmp_path / "checkout"
    pkg = root / "chipbench"
    shutil.copytree(ROOT / "chipbench", pkg)
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    (pkg / "sources" / "paced.py").write_text(PACED)
    (pkg / "traffic" / "paced200.json").write_text(json.dumps(
        {"kind": "paced", "batch": 512, "calls_per_s": 200,
         "why": "random rows at a fixed call rate"}))
    (pkg / "metrics" / "served_rows.py").write_text(
        "def read(rec):\n    return float(rec['rows'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "jane_fin.paced200"
    bench["workloads"].append({"name": cell, "config": "jane_fin",
                               "traffic": "paced200", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "fin_p99_ms":
            m["workloads"].append(cell)
    for name in ("served_rows", "device_idle_pct.paced"):
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "device",
            "moves": "fin_p99_ms", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = tiny_spec(cell, root, pkg)
    spec["traffic"]["batch"] = 512
    assert spec["traffic"]["kind"] == "paced"
    assert [m["name"] for m in spec["per_layer"]] == [
        "served_rows", "device_idle_pct.paced"]
    assert cells.reader("served_rows", pkg)({"rows": 7}) == 7.0
    idle = cells.reader("device_idle_pct.paced", pkg)
    assert idle({"window_s": 2.0, "busy_s": 0.5}) == 75.0
    res = run.run_cell(spec, 3, 0.5, False)
    assert res["correct"] and set(res["metrics"]) == {"fin_p99_ms",
                                                      "setup_s"}
    assert 50 <= res["attempted"] <= 101          # 200 calls/s for 0.5 s
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("seed", [2**31 + 11, 2**62 + 3])
def test_the_result_line_holds_the_contract_keys(seed):
    spec = tiny_spec("jane_fin.b2048")
    res = run.run_cell(spec, seed, 0.5, False)
    assert list(res) == RESULT_KEYS
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    names = {m["name"] for m in spec["end_to_end"]}
    assert set(res["metrics"]) == names
    for v in res["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(res)


def test_no_tpu_means_no_run_and_no_result(capsys):
    assert run.main(["--workload", "jane_fin.b2048", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 1
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert not any(line.startswith("{") for line in out.out.splitlines())


def test_a_server_key_the_program_does_not_take_is_refused():
    spec = tiny_spec("jane_fin.b2048")
    spec["config"]["server"]["evict_age"] = 15.0
    with pytest.raises(TypeError, match="evict_age"):
        run.run_cell(spec, 4, 0.5, False)


def test_an_unknown_traffic_kind_is_refused():
    spec = tiny_spec("jane_fin.b2048")
    spec["traffic"]["kind"] = "bursts"
    with pytest.raises(KeyError, match="bursts"):
        run.run_cell(spec, 4, 0.5, False)
