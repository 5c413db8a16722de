"""Seeded gradient-boosted trees made by the benchmark (numpy only).

A boosted forest here is XGBoost's binary-logistic model in the heap
layout the program's trees read: per tree ``feat``/``thresh`` for the
2**depth - 1 internal nodes (``x > thresh`` goes right) and one weight per
leaf; the margin is ``base_score + learning_rate * sum of leaf weights``.
The benchmark makes it itself, so the reference depends on nothing the
program computed.

Trees are complete to ``depth``. Each tree draws its own column sample
(``colsample_bytree`` of the features, XGBoost's per-tree sample) and a
row sample without replacement (``subsample``), in random order. Each
node takes a feature drawn from its tree's column sample and, as
threshold, the smaller of that feature's values at the first and the
last of the sample's rows that reach the node: where the two differ,
both children get rows, so the split is live. A node no row reaches
takes the value of a row drawn from the tree's whole sample.

Leaf weights are seeded, not fitted: each is drawn from N(0, leaf_sd**2).
A leaf's XGBoost weight is one Newton step, -G / (H + lambda); at margin
0 on balanced labels, with lambda 1 and the ~9 training rows a leaf of a
depth-11 tree holds here, its spread is about 0.5, the default
``leaf_sd``. Fitted weights would follow the labels of the seeded rows,
whose loadings differ from those of the pool the cell serves (every
seed draws its own), and push nearly every margin below 0; drawn ones
keep the backend's answers split between both classes, so each tree's
leaf counts in every answer.
"""

from __future__ import annotations

import numpy as np


def make_boosted(x: np.ndarray, *, n_trees: int, depth: int,
                 learning_rate: float, subsample: float,
                 colsample_bytree: float, base_score: float, seed,
                 leaf_sd: float = 0.5) -> dict:
    """-> {"feat" (T, H) int32, "thresh" (T, H) float32, "leaf" (T, 2**depth)
    float32, "learning_rate", "base_score"}, thresholds from the training
    rows ``x`` (N, F) float32."""
    x = np.asarray(x, np.float32)
    n, n_feat = x.shape
    rng = np.random.default_rng(seed)
    n_nodes = (1 << depth) - 1
    n_rows = max(2, int(round(subsample * n)))
    n_cols = max(1, int(round(colsample_bytree * n_feat)))
    feat = np.empty((n_trees, n_nodes), np.int32)
    thresh = np.empty((n_trees, n_nodes), np.float32)
    flat = x.ravel()
    for t in range(n_trees):
        at = rng.choice(n, n_rows, replace=False) * n_feat  # row offsets
        cols = rng.choice(n_feat, n_cols, replace=False)
        node = np.zeros(n_rows, np.int64)            # index within the level
        pos = np.arange(n_rows)
        for level in range(depth):
            width = 1 << level
            f = cols[rng.integers(0, n_cols, width)]
            first, last = rng.integers(0, n_rows, (2, width))
            first[node[::-1]] = pos[::-1]
            last[node] = pos
            v = np.minimum(flat[at[first] + f], flat[at[last] + f])
            feat[t, width - 1:2 * width - 1] = f
            thresh[t, width - 1:2 * width - 1] = v
            node = 2 * node + (flat[at + f[node]] > v[node])
    leaf = rng.normal(0.0, leaf_sd, (n_trees, 1 << depth)).astype(np.float32)
    return dict(feat=feat, thresh=thresh, leaf=leaf,
                learning_rate=float(learning_rate),
                base_score=float(base_score))
