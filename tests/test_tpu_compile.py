"""The main path's Pallas kernels compile for a TPU v5e — without the chip.

The TPU compiler is installed beside the CPU backend, and it compiles
for a chip that is described and not attached. Each test lowers one
kernel at the width the served path uses (``interpret=False`` passed
explicitly: on CPU ``resolve_interpret(None)`` would pick the
interpreter) and asserts that the compiled program holds the kernel as
a ``tpu_custom_call``. This catches what interpret mode never checks:
tile alignment, vector layouts and VMEM use that Mosaic refuses.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so describing it
while pytest-xdist workers import this file would break collection. The
persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.artifact import finalize_artifact
from repro.core.mapping import map_svm, map_tree_ensemble
from repro.data.janestreet_like import (SWITCH_FEATURES,
                                        make_janestreet_like,
                                        train_test_split)
from repro.ml.svm import fit_linear_svm
from repro.ml.trees import fit_random_forest

# module objects (``repro.kernels`` re-exports functions of the same names)
bucketize = importlib.import_module("repro.kernels.bucketize")
classical_lookup = importlib.import_module("repro.kernels.classical_lookup")
ensemble_lookup = importlib.import_module("repro.kernels.ensemble_lookup")
evict = importlib.import_module("repro.kernels.evict")
stream_update = importlib.import_module("repro.kernels.stream_update")

N_BUCKETS = 1 << 18          # the switch-scale register file (8 MiB)
WINDOW = 1024
CHUNK_WINDOWS = 16
TPU_LANE = 128               # core.artifact.default_lane() on a TPU


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(one_chip, no_persistent_cache):
    """-> shape(array or shape tuple, dtype) placed on the described chip."""
    def shape(a, dtype=None):
        if isinstance(a, tuple):
            return jax.ShapeDtypeStruct(a, dtype or jnp.float32,
                                        sharding=one_chip)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    return shape


def _tpu_layout(art):
    """The artifact as a TPU finalizes it: 128-lane padded flat tables."""
    return finalize_artifact(
        dataclasses.replace(art, ftable_flat=None, dtable_flat=None,
                            dtable_pad=None, vtable_flat=None),
        lane=TPU_LANE)


@pytest.fixture(scope="module")
def flow_models():
    """The streaming benches' models (benchmarks.common.trace_models) on a
    short trace: the table shapes depend on the forests, not the trace
    length. -> (switch artifact, backend)."""
    from benchmarks.common import trace_models
    from repro.netsim.packets import synth_trace
    return trace_models(synth_trace(n_flows=4000, seed=0), N_BUCKETS)


@pytest.fixture(scope="module")
def artifacts(flow_models):
    """name -> (artifact in the TPU layout, batch rows served)."""
    xtr, ytr, _, _ = train_test_split(*make_janestreet_like(20000, seed=0))
    xsw = np.asarray(xtr[:, SWITCH_FEATURES], np.float32)
    finance = map_tree_ensemble(
        fit_random_forest(xsw, ytr, n_classes=2, n_trees=10, max_depth=5,
                          seed=0), xsw.shape[1])
    svm = map_svm(fit_linear_svm(xsw, ytr, n_classes=2, seed=0), xsw)
    return {"trace_rf4x3": (_tpu_layout(flow_models[0]),
                            CHUNK_WINDOWS * WINDOW),
            "finance_rf10x5": (_tpu_layout(finance), 2048),
            "finance_svm": (_tpu_layout(svm), 2048)}


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text: str, count: int = 1):
    assert text.count('custom_call_target="tpu_custom_call"') == count


def test_stream_update_compiles(chip):
    fn = lambda r, b, t, ln, f, v: stream_update.stream_update_pallas(
        r, b, t, ln, f, v, limit=float(1 << 24), interpret=False)
    _assert_kernel(_compiled_text(
        fn, chip((8, N_BUCKETS)), chip((WINDOW,), jnp.int32),
        chip((WINDOW,)), chip((WINDOW,)), chip((WINDOW,)),
        chip((WINDOW,), jnp.bool_)))


def test_evict_fill_compiles(chip):
    fn = lambda r, m, f: evict.evict_fill_pallas(r, m, f, interpret=False)
    _assert_kernel(_compiled_text(fn, chip((8, N_BUCKETS)),
                                  chip((N_BUCKETS,), jnp.bool_),
                                  chip((8,))))


@pytest.mark.parametrize("name", ["trace_rf4x3", "finance_rf10x5"])
def test_bucketize_compiles(chip, artifacts, name):
    art, rows = artifacts[name]
    fn = lambda x, e: bucketize.bucketize_pallas(x, e, interpret=False)
    _assert_kernel(_compiled_text(
        fn, chip((rows, art.edges.shape[0])), chip(art.edges)))


@pytest.mark.parametrize("select", ["matmul", "compare"])
@pytest.mark.parametrize("name", ["trace_rf4x3", "finance_rf10x5"])
def test_ensemble_lookup_fused_compiles(chip, artifacts, name, select):
    art, rows = artifacts[name]
    fn = lambda x, e, ft, df, dp: ensemble_lookup.ensemble_lookup_fused(
        x, e, ft, df, dp, interpret=False, select=select)
    _assert_kernel(_compiled_text(
        fn, chip((rows, art.edges.shape[0])), chip(art.edges),
        chip(art.ftable_flat), chip(art.dtable_flat), chip(art.dtable_pad)))


def test_classical_lookup_fused_compiles(chip, artifacts):
    art, rows = artifacts["finance_svm"]
    fn = lambda x, e, v: classical_lookup.classical_lookup_fused(
        x, e, v, interpret=False)
    _assert_kernel(_compiled_text(
        fn, chip((rows, art.edges.shape[0])), chip(art.edges),
        chip(art.vtable_flat)))


def test_chunk_megastep_compiles_with_both_kernels(chip, flow_models,
                                                   monkeypatch):
    """The whole served chunk step at deployment size (2^18 buckets,
    W=1024, K=16) routes through the register-scan kernel and the fused
    classify kernel. The server reads the platform from
    ``jax.default_backend()``; the test steers it to the chip being
    compiled for, so the server resolves exactly as it does on a TPU."""
    from repro.netsim.stream import PacketChunk
    from repro.serving.stream_serving import StreamingHybridServer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    art, backend = flow_models
    srv = StreamingHybridServer(
        _tpu_layout(art), backend, n_buckets=N_BUCKETS, window=WINDOW,
        chunk_windows=CHUNK_WINDOWS, capacity=64, threshold=0.9)
    assert srv.use_pallas
    kw = (CHUNK_WINDOWS, WINDOW)
    chunk = PacketChunk(bucket=chip(kw, jnp.int32), ts=chip(kw),
                        length=chip(kw), is_fwd=chip(kw),
                        valid=chip(kw, jnp.bool_))
    compiled = srv._chunk_step.lower(
        jax.tree.map(chip, srv.artifact), jax.tree.map(chip, srv.state),
        jax.tree.map(chip, srv.stats), chunk,
        chip((), jnp.float32)).compile()
    _assert_kernel(compiled.as_text(), count=2)
    # the register file is donated and aliased in place
    assert compiled.memory_analysis().alias_size_in_bytes >= 8 * N_BUCKETS * 4


def test_a_grown_forest_backend_compiles_as_step_arguments(chip, artifacts,
                                                           monkeypatch):
    """The finance per-request step with the benchmark's fully grown
    backend shape (100 trees of node arrays padded to 32,768 nodes, 52
    levels, on 130-feature rows) compiles for the chip with the fused
    classify kernel, and takes the forest's 65.5 MB as arguments: the
    compiled program's own code stays a small fraction of it."""
    from jax.tree_util import Partial

    from repro.ml.trees import TreeEnsemble, predict_tree_ensemble
    from repro.serving.hybrid_serving import HybridServer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_trees, m = 100, 1 << 15
    ens = TreeEnsemble(feat=np.zeros((n_trees, m), np.int32),
                       thresh=np.zeros((n_trees, m), np.float32),
                       leaf=np.zeros((n_trees, m, 2), np.float32),
                       left=np.zeros((n_trees, m), np.int32), depth=52)
    art, rows = artifacts["finance_rf10x5"]
    srv = HybridServer(art, Partial(predict_tree_ensemble, ens),
                       capacity=1024, switch_features=SWITCH_FEATURES)
    compiled = srv._step.lower(
        jax.tree.map(chip, srv.artifact), chip((rows, 130)),
        chip((), jnp.float32), jax.tree.map(chip, srv._backend)).compile()
    _assert_kernel(compiled.as_text())
    mem = compiled.memory_analysis()
    forest = n_trees * m * 20
    assert mem.argument_size_in_bytes >= forest
    assert mem.generated_code_size_in_bytes < forest // 4
