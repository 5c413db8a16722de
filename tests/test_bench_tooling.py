"""Benchmark-suite tooling: fail-fast suite runner + bench-v1 validation.

Two CI-trust contracts:

* ``benchmarks/run.py --all-suites`` must exit nonzero the moment a
  sub-suite fails (propagating its exit code), so an oracle failure in
  any emitter can never leave CI green;
* every ``BENCH_*.json`` must satisfy the bench-v1 schema before it is
  uploaded into the perf trajectory — ``benchmarks.validate_schema``
  is the gate and must reject malformed payloads.
"""

import copy
import json
import sys
import types
from pathlib import Path

import pytest

from benchmarks.common import write_bench_json
from benchmarks.run import EXTRA_SUITES, run_suites
from benchmarks.validate_schema import (SchemaError, main as validate_main,
                                        validate_bench_json,
                                        validate_bench_payload)


# ---------------------------------------------------------------------------
# fail-fast suite runner
# ---------------------------------------------------------------------------

def test_run_suites_propagates_child_failure():
    """A failing suite must abort the run with a nonzero exit code, not
    be swallowed into a summary."""
    with pytest.raises(SystemExit) as e:
        run_suites(("definitely_not_a_bench_module",))
    assert e.value.code not in (0, None)


def test_run_suites_failure_is_fail_fast(capfd):
    """The first failure stops the run: the suite after it never
    launches (its banner is never printed)."""
    with pytest.raises(SystemExit):
        run_suites(("definitely_not_a_bench_module", "also_never_reached"))
    out = capfd.readouterr()
    assert "benchmarks.definitely_not_a_bench_module" in out.out
    assert "also_never_reached" not in out.out


def test_run_suites_empty_returns_cleanly():
    assert run_suites(()) is None


@pytest.fixture()
def fake_suite(monkeypatch):
    """A stand-in ``benchmarks._fake_suite`` whose main runs ``body``."""
    mod = types.ModuleType("benchmarks._fake_suite")
    monkeypatch.setitem(sys.modules, "benchmarks._fake_suite", mod)
    return mod


@pytest.mark.parametrize("exc, code", [(SystemExit(3), 3),
                                       (RuntimeError("oracle"), 1)])
def test_run_suites_in_process_exit_codes(fake_suite, exc, code):
    """Suites run in this process through main(argv): a nonzero
    SystemExit keeps its code, an exception exits 1."""
    def main(argv):
        raise exc
    fake_suite.main = main
    with pytest.raises(SystemExit) as e:
        run_suites(("_fake_suite",))
    assert e.value.code == code


def test_run_suites_passes_quick_to_main(fake_suite):
    seen = []
    fake_suite.main = seen.append
    run_suites(("_fake_suite",), quick=True)
    run_suites(("_fake_suite",))
    assert seen == [["--quick"], []]


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing else
    is named; otherwise the cache sits at <checkout>/.jax_cache."""
    import jax
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "untouched")
        monkeypatch.setenv(cc.ENV_VAR, "/from/env")
        assert cc.configure_compile_cache() == "/from/env"
        assert jax.config.jax_compilation_cache_dir == "untouched"
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.configure_compile_cache() == str(cc.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(cc.CHECKOUT_CACHE)
        assert cc.CHECKOUT_CACHE == (Path(__file__).resolve().parents[1]
                                     / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_all_suites_list_covers_every_emitter():
    """The --all-suites chain names each standalone bench-v1 emitter,
    including the cross-window batching, adversarial-scenario,
    ingest-latency, observability and resource-fit benches."""
    assert set(EXTRA_SUITES) == {"kernel_microbench", "stream_bench",
                                 "shard_stream_bench", "batch_bench",
                                 "scenario_bench", "latency_bench",
                                 "obs_bench", "analysis_bench"}


# ---------------------------------------------------------------------------
# bench-v1 schema validation
# ---------------------------------------------------------------------------

@pytest.fixture()
def valid_bench(tmp_path, monkeypatch):
    """A real emitter-written file (write_bench_json is the single writer
    every suite goes through, so validating its output validates them)."""
    monkeypatch.chdir(tmp_path)
    path = write_bench_json(
        "BENCH_t.json", "batch",
        [{"name": "batch_serving", "paper_ref": "§2.2.1", "ok": True,
          "wall_s": 0.1, "rows": [{"flush_every": 4, "pkts_per_s": 1.0}]}],
        config={"flush_every": [1, 4]})
    return tmp_path / path


def test_validator_accepts_emitter_output(valid_bench):
    payload = validate_bench_json(str(valid_bench))
    assert payload["suite"] == "batch"


def test_validator_accepts_checked_in_trajectory(pytestconfig):
    """Every BENCH_*.json currently in the repo root is schema-valid."""
    root = pytestconfig.rootpath
    files = sorted(root.glob("BENCH_*.json"))
    assert files, "no BENCH_*.json checked in next to the tests"
    for f in files:
        validate_bench_json(str(f))


@pytest.mark.parametrize("mutate", [
    lambda p: p.pop("schema"),
    lambda p: p.update(schema="bench-v2"),
    lambda p: p.pop("benches"),
    lambda p: p.update(benches=[]),
    lambda p: p.update(benches=[{"name": "x"}]),          # missing keys
    lambda p: p["benches"][0].update(ok="yes"),           # wrong type
    lambda p: p["benches"][0].update(wall_s="fast"),      # wrong type
    lambda p: p.update(config=None),
])
def test_validator_rejects_malformed_payloads(valid_bench, mutate):
    payload = json.loads(valid_bench.read_text())
    mutate(payload)
    with pytest.raises(SchemaError):
        validate_bench_payload(copy.deepcopy(payload), "mutated")


def test_validator_cli_exits_nonzero_on_malformed_file(valid_bench,
                                                       tmp_path):
    bad = tmp_path / "BENCH_bad.json"
    payload = json.loads(valid_bench.read_text())
    payload["benches"][0].pop("wall_s")
    bad.write_text(json.dumps(payload))
    validate_main([str(valid_bench)])             # good file: returns
    with pytest.raises(SystemExit) as e:
        validate_main([str(bad)])
    assert e.value.code not in (0, None)
    with pytest.raises(SystemExit):               # not-JSON is also caught
        bad.write_text("{not json")
        validate_main([str(bad)])


def test_validator_cli_requires_files(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)                   # no BENCH_*.json here
    with pytest.raises(SystemExit) as e:
        validate_main([])
    assert e.value.code not in (0, None)


def test_validator_rejects_unknown_suite(valid_bench):
    """A typo'd / unregistered suite tag must fail with a message that
    names the offender and the registry to fix."""
    payload = json.loads(valid_bench.read_text())
    payload["suite"] = "mystery_suite"
    with pytest.raises(SchemaError, match="unknown suite 'mystery_suite'"):
        validate_bench_payload(payload, "mutated")


def test_validator_cli_messages_are_pointed(valid_bench, tmp_path):
    """The CLI exit message must say *what* is malformed and *where* —
    a bare nonzero exit would send the operator spelunking."""
    cases = [
        (lambda p: p.pop("benches"), "missing top-level key 'benches'"),
        (lambda p: p["benches"][0].update(ok="yes"), "'ok' must be"),
        (lambda p: p.update(suite="mystery_suite"),
         "unknown suite 'mystery_suite'"),
    ]
    for i, (mutate, needle) in enumerate(cases):
        payload = json.loads(valid_bench.read_text())
        mutate(payload)
        bad = tmp_path / f"BENCH_bad{i}.json"
        bad.write_text(json.dumps(payload))
        with pytest.raises(SystemExit) as e:
            validate_main([str(bad)])
        assert e.value.code not in (0, None)
        assert needle in str(e.value.code)
        assert str(bad) in str(e.value.code)      # names the offending file


def _analysis_payload():
    return {
        "schema": "bench-v1", "suite": "analysis", "generated_unix": 0.0,
        "backend": "cpu", "config": {},
        "benches": [{"name": "device_fit", "paper_ref": "Tables 1-2",
                     "ok": True, "wall_s": 0.1,
                     "rows": [{"artifact": "rf", "profile": "tofino_like",
                               "fits": True, "util_stages": 0.25,
                               "util_sram_kib": 0.1, "util_tcam_kib": 0.1,
                               "util_entries": 0.1, "util_tables": 0.5},
                              {"artifact": "xgb", "profile": "tight_test",
                               "fits": False, "guard": "FitError"}]}],
    }


def test_validator_analysis_rows_require_utilization():
    validate_bench_payload(_analysis_payload(), "ok")  # guard row exempt
    for strip in ("artifact", "fits", "util_entries", "util_sram_kib"):
        payload = _analysis_payload()
        payload["benches"][0]["rows"][0].pop(strip)
        with pytest.raises(SchemaError, match=strip):
            validate_bench_payload(payload, "stripped")
    payload = _analysis_payload()
    payload["benches"][0]["rows"][0]["fits"] = "yes"      # wrong type
    with pytest.raises(SchemaError, match="fits"):
        validate_bench_payload(payload, "typed")


def _shard_payload():
    return {
        "schema": "bench-v1", "suite": "shard", "generated_unix": 0.0,
        "backend": "cpu", "config": {},
        "benches": [{"name": "shard_stream", "paper_ref": "§5",
                     "ok": True, "wall_s": 0.1,
                     "rows": [{"devices": 4, "d_shard": 2, "d_data": 2,
                               "classify_rows_per_device": 128,
                               "pkts_per_s": 1000.0},
                              {"note": "summary row, no device count"}]}],
    }


def test_validator_shard_rows_require_mesh_shape():
    validate_bench_payload(_shard_payload(), "ok")     # summary row exempt
    for strip in ("d_shard", "d_data", "classify_rows_per_device"):
        payload = _shard_payload()
        payload["benches"][0]["rows"][0].pop(strip)
        with pytest.raises(SchemaError, match=strip):
            validate_bench_payload(payload, "stripped")
    payload = _shard_payload()
    payload["benches"][0]["rows"][0]["classify_rows_per_device"] = 12.5
    with pytest.raises(SchemaError, match="classify_rows_per_device"):
        validate_bench_payload(payload, "typed")


def _latency_payload():
    return {
        "schema": "bench-v1", "suite": "latency", "generated_unix": 0.0,
        "backend": "cpu", "config": {},
        "benches": [{"name": "ingest_latency", "paper_ref": "§5",
                     "ok": True, "wall_s": 0.1,
                     "rows": [{"config": "prefetch_on", "prefetch": True,
                               "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0,
                               "bit_identical": True},
                              {"config": "autotune",
                               "chunk_windows": 16}]}],
    }


def test_validator_latency_rows_require_percentiles():
    validate_bench_payload(_latency_payload(), "ok")   # autotune row exempt
    for strip in ("p50_ms", "p95_ms", "p99_ms", "bit_identical"):
        payload = _latency_payload()
        payload["benches"][0]["rows"][0].pop(strip)
        with pytest.raises(SchemaError, match=strip):
            validate_bench_payload(payload, "stripped")
    payload = _latency_payload()
    payload["benches"][0]["rows"][0]["p95_ms"] = "slow"   # wrong type
    with pytest.raises(SchemaError, match="p95_ms"):
        validate_bench_payload(payload, "typed")
