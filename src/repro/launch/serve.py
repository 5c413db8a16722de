"""Serving launcher: hybrid IIsy switch tier + tree-ensemble backend.

``python -m repro.launch.serve --use-case anomaly --threshold 0.7``
trains the small switch model + large backend on the synthetic use-case
data, stands up the HybridServer, runs batched requests through it, and
prints the paper's telemetry (fraction handled, misclassification).

``--use-case finance`` is the paper's finance deployment: the switch
parses 5 of a trade's 130 features (``switch_features``) and the XGBoost
backend scores the forwarded trades on all 130, both in one fused step.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.mapping import map_tree_ensemble
from repro.ml.metrics import accuracy, precision_recall_f1
from repro.ml.trees import (fit_random_forest, fit_xgboost,
                            predict_tree_ensemble)
from repro.serving.hybrid_serving import HybridServer


def build_usecase(name: str, n=20000, seed=0):
    if name == "anomaly":
        from repro.data.unsw_like import make_unsw_like, train_test_split
        x, y = make_unsw_like(n, seed=seed, n_features=5)
        return train_test_split(x, y)
    from repro.data.janestreet_like import (make_janestreet_like,
                                            train_test_split)
    x, y = make_janestreet_like(n, seed=seed)
    return train_test_split(x, y)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--use-case", default="anomaly",
                    choices=["anomaly", "finance"])
    ap.add_argument("--threshold", type=float, default=0.7)
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--switch-trees", type=int, default=10)
    ap.add_argument("--switch-depth", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2048)
    args = ap.parse_args(argv)

    xtr, ytr, xte, yte = build_usecase(args.use_case)
    switch_features = None
    if args.use_case == "finance":
        from repro.data.janestreet_like import SWITCH_FEATURES
        switch_features = SWITCH_FEATURES
    xsw_tr = xtr if switch_features is None else xtr[:, switch_features]

    # small switch model (paper Table 3 "Medium") + big backend
    small = fit_random_forest(xsw_tr, ytr, n_classes=2,
                              n_trees=args.switch_trees,
                              max_depth=args.switch_depth, seed=0)
    art = map_tree_ensemble(small, xsw_tr.shape[1])

    # the backend scores the forwarded rows on every feature
    big = fit_xgboost(xtr, ytr, n_trees=60, max_depth=6)

    def backend_fn(rows):
        return predict_tree_ensemble(big, rows)

    server = HybridServer(art, backend_fn, threshold=args.threshold,
                          capacity=args.capacity,
                          switch_features=switch_features)

    n = xte.shape[0]
    preds = []
    t0 = time.time()
    for lo in range(0, n - args.batch + 1, args.batch):
        pred, stats = server.classify(xte[lo:lo + args.batch])
        preds.append(np.asarray(pred))
    pred = np.concatenate(preds)
    m = len(pred)
    acc = accuracy(yte[:m], pred)
    p, r, f1 = precision_recall_f1(yte[:m], pred)
    print(f"use_case={args.use_case} tau={args.threshold}")
    print(f"acc={acc:.4f} precision={p:.4f} recall={r:.4f} f1={f1:.4f}")
    print(f"handled_at_switch={stats.fraction_handled:.3f} "
          f"backend_rows/batch={stats.backend_rows}/{args.batch} "
          f"wall={time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
