"""Shared benchmark plumbing: use-case data, model zoo, table printing,
and the machine-readable BENCH_*.json emission (schema "bench-v1")."""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import configure_compile_cache

# Persistent compilation cache for every bench entry point importing this
# module: the quick CI suites are compile-dominated (tens of seconds of
# XLA work for seconds of compute), and the jitted steps are identical
# run to run — cached executables cut reruns to the actual measurement.
# JAX_COMPILATION_CACHE_DIR redirects it (empty: off); otherwise it sits
# at a fixed path inside the checkout.
configure_compile_cache()

from repro.core.mapping import (map_kmeans, map_naive_bayes, map_svm,
                                map_tree_ensemble)
from repro.data.janestreet_like import SWITCH_FEATURES
from repro.ml.kmeans import fit_kmeans, predict_kmeans
from repro.ml.metrics import accuracy, precision_recall_f1
from repro.ml.naive_bayes import fit_gaussian_nb, predict_nb
from repro.ml.svm import fit_linear_svm, predict_svm
from repro.ml.trees import (fit_decision_tree, fit_random_forest,
                            fit_xgboost, predict_margin_xgboost,
                            predict_tree_ensemble)

MODELS = ("SVM", "Bayes", "KMeans", "DT", "RF", "XGB")


def load_usecase(name: str, n=20000, seed=0, switch_features=True):
    """-> (xtr, ytr, xte, yte) with the paper's 5 switch features."""
    if name == "anomaly":
        from repro.data.unsw_like import make_unsw_like, train_test_split
        x, y = make_unsw_like(n, seed=seed, n_features=5)
        return train_test_split(x, y)
    from repro.data.janestreet_like import make_janestreet_like, \
        train_test_split
    x, y = make_janestreet_like(n, seed=seed)
    if switch_features:
        x = x[:, SWITCH_FEATURES]
    return train_test_split(x, y)


def fit_and_map(model: str, xtr, ytr, *, n_bins=64, action_bits=16,
                n_trees=10, max_depth=5, seed=0):
    """Train one switch-size model and map it. -> (direct_fn, artifact)."""
    f = xtr.shape[1]
    if model == "SVM":
        m = fit_linear_svm(xtr, ytr, n_classes=2, seed=seed)
        return (lambda x: predict_svm(m, x),
                map_svm(m, xtr, n_bins=n_bins, action_bits=action_bits), m)
    if model == "Bayes":
        m = fit_gaussian_nb(xtr, ytr, n_classes=2)
        return (lambda x: predict_nb(m, x),
                map_naive_bayes(m, xtr, n_bins=n_bins,
                                action_bits=action_bits), m)
    if model == "KMeans":
        m = fit_kmeans(xtr, k=2, seed=seed)
        # align cluster->class by majority vote on train
        assign = np.asarray(predict_kmeans(m, xtr))
        maj = [int(np.round(np.mean(np.asarray(ytr)[assign == c]))
                   if np.any(assign == c) else c) for c in range(2)]
        flip = maj[0] == 1

        def direct(x):
            p = predict_kmeans(m, x)
            return 1 - p if flip else p

        art = map_kmeans(m, xtr, n_bins=n_bins, action_bits=action_bits)
        art.flip = flip
        return (direct, art, m)
    if model == "DT":
        m = fit_decision_tree(xtr, ytr, n_classes=2, max_depth=max_depth)
        return (lambda x: predict_tree_ensemble(m, x),
                map_tree_ensemble(m, f, action_bits=action_bits), m)
    if model == "RF":
        m = fit_random_forest(xtr, ytr, n_classes=2, n_trees=n_trees,
                              max_depth=max_depth, seed=seed)
        return (lambda x: predict_tree_ensemble(m, x),
                map_tree_ensemble(m, f, action_bits=action_bits), m)
    if model == "XGB":
        m = fit_xgboost(xtr, ytr, n_trees=n_trees, max_depth=max_depth)
        return (lambda x: predict_tree_ensemble(m, x),
                map_tree_ensemble(m, f, action_bits=action_bits), m)
    raise ValueError(model)


def table_pred_maybe_flip(art, x):
    from repro.core.inference import table_predict
    pred, conf = table_predict(art, x)
    if getattr(art, "flip", False):
        pred = 1 - pred
    return pred, conf


def trace_models(trace, n_buckets, *, small=(4, 3, 0), big=(16, 6, 1)):
    """Switch-size RF artifact + backend RF for a synthetic packet trace.
    -> (artifact, backend_fn).

    The streaming-bench model recipe (previously copy-pasted across
    stream_bench, shard_stream_bench and scenario_bench): train both
    forests on the trace's own batch flow features — one row per flow,
    read out at the flow's bucket — map the small (n_trees, max_depth,
    seed) forest to the switch table artifact and close the big one over
    ``predict_tree_ensemble`` as the row-wise backend."""
    from repro.netsim.features import flow_features
    b, table = flow_features(trace, n_buckets=n_buckets)
    first_idx = np.unique(np.asarray(trace.flow_id), return_index=True)[1]
    rows = np.asarray(table)[np.asarray(b)[first_idx]].astype(np.float32)
    s_trees, s_depth, s_seed = small
    b_trees, b_depth, b_seed = big
    sm = fit_random_forest(rows, trace.flow_label, n_classes=2,
                           n_trees=s_trees, max_depth=s_depth, seed=s_seed)
    bg = fit_random_forest(rows, trace.flow_label, n_classes=2,
                           n_trees=b_trees, max_depth=b_depth, seed=b_seed)
    return map_tree_ensemble(sm, rows.shape[1]), \
        (lambda r: predict_tree_ensemble(bg, r))


def jsonable(obj):
    """Best-effort conversion of benchmark rows to JSON-safe values."""
    if isinstance(obj, dict):
        return {str(k): jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [jsonable(v) for v in obj]
    if isinstance(obj, (bool, np.bool_)):
        return bool(obj)
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, (np.ndarray, jax.Array)):
        return jsonable(np.asarray(obj).tolist())
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


def write_bench_json(path, suite, benches, config=None):
    """Write one BENCH_*.json file (schema "bench-v1").

    benches: list of dicts with keys name, paper_ref, wall_s, ok, rows —
    rows being whatever the bench's run() returned (tables keep the
    [headers-implied] row-list form the printed tables use). config
    records the run parameters (sample size, subset, iters) so partial
    --quick/--only runs are distinguishable in the trajectory.
    """
    payload = {
        "schema": "bench-v1",
        "suite": suite,
        "generated_unix": time.time(),
        "backend": jax.default_backend(),
        "config": jsonable(config or {}),
        "benches": [jsonable(b) for b in benches],
    }
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    print(f"[wrote {path}]")
    return path


def print_table(title, headers, rows):
    print(f"\n## {title}")
    widths = [max(len(str(h)), max((len(str(r[i])) for r in rows),
                                   default=0)) for i, h in enumerate(headers)]
    line = " | ".join(str(h).ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("-+-".join("-" * w for w in widths))
    for r in rows:
        print(" | ".join(str(c).ljust(w) for c, w in zip(r, widths)))
