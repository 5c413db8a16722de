"""Seeded tree ensembles made by the benchmark, and their plain evaluation.

A forest here is the model's weights: complete binary trees of a fixed
depth in heap layout (``feat``/``thresh`` per internal node, class counts
per leaf), the layout the program's mapping tool and backend predictor
read. The benchmark makes them itself, from the run's seed, so that the
reference below depends on nothing the program computed.

Shapes are fixed by the configuration, not by the seed. The program maps
a switch forest to tables whose sizes follow from how many thresholds
each tree puts on each feature, and a table of another size is another
compiled program. So each tree's multiset of node features comes from the
configuration's ``structure_seed``; the run's seed decides (through the
bootstrap sample and the thresholds drawn) which node takes which of
those features, and every threshold and leaf. Thresholds are continuous
draws, so no two coincide and every table keeps its size.

Splitting is extremely-randomized (Geurts et al., Machine Learning 2006):
``candidates`` uniform thresholds between the node's smallest and largest
training value of each feature it may still take, the pair with the
lowest Gini impurity kept. A node with nothing to split takes a remaining
feature with a threshold drawn over its whole training range, and a leaf
no training row reaches takes its parent's counts.
"""

from __future__ import annotations

import numpy as np


def node_features(n_trees: int, depth: int, n_features: int,
                  structure_seed: int) -> np.ndarray:
    """(T, 2**depth - 1): each tree's multiset of node features, as even a
    spread over the features as the node count allows, which features
    get the extra nodes fixed by ``structure_seed``."""
    rng = np.random.default_rng(structure_seed)
    n_nodes = (1 << depth) - 1
    base = np.arange(n_nodes) % n_features
    return np.stack([rng.permutation(n_features)[base]
                     for _ in range(n_trees)]).astype(np.int32)


def _gini(counts: np.ndarray) -> np.ndarray:
    n = counts.sum(axis=-1)
    p = counts / np.maximum(n, 1)[..., None]
    return n * (1.0 - (p * p).sum(axis=-1))


def _best_split(v: np.ndarray, yl: np.ndarray, feats, n_classes: int,
                candidates: int, rng) -> tuple:
    """Best (feature, threshold, score) over ``candidates`` uniform
    thresholds for each feature in ``feats`` on the node's rows ``v``
    (m, F); None when no feature varies."""
    counts = np.bincount(yl, minlength=n_classes)
    onehot = yl[:, None] == np.arange(n_classes)[None, :]      # (m, C)
    best = None
    for f in feats:
        col = v[:, f]
        lo, hi = float(col.min()), float(col.max())
        if not lo < hi:
            continue
        cand = np.float32(lo + rng.random(candidates) * (hi - lo))
        cand = np.minimum(cand, np.nextafter(np.float32(hi),
                                             np.float32(-np.inf)))
        left = (col[None, :] <= cand[:, None]).astype(np.int64)  # (k, m)
        lc = left @ onehot                                      # (k, C)
        score = _gini(lc) + _gini(counts[None, :] - lc)
        j = int(np.argmin(score))
        if best is None or score[j] < best[2]:
            best = (f, cand[j], score[j])
    return best


def fit_forest(x: np.ndarray, y: np.ndarray, *, n_trees: int, depth: int,
               n_classes: int, structure_seed: int, seed,
               candidates: int = 8) -> dict:
    """-> {"feat" (T, H) int32, "thresh" (T, H) float32,
    "leaf" (T, 2**depth, C) float32} fitted on rows ``x`` (N, F).

    Nodes are filled level by level; each takes, from its tree's features
    not yet placed, the feature and threshold of lowest Gini impurity."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int64)
    n, n_feat = x.shape
    rng = np.random.default_rng(seed)
    multiset = node_features(n_trees, depth, n_feat, structure_seed)
    n_nodes = (1 << depth) - 1
    feat = np.empty((n_trees, n_nodes), np.int32)
    thresh = np.empty((n_trees, n_nodes), np.float32)
    leaf = np.empty((n_trees, 1 << depth, n_classes), np.float32)
    gmin, gmax = x.min(axis=0), x.max(axis=0)
    for t in range(n_trees):
        left_over = list(rng.permutation(multiset[t]))
        rows = rng.integers(0, n, n)                       # bootstrap
        xt, yt = x[rows], y[rows]
        node = np.zeros(n, np.int64)                       # heap index
        counts_of = {}
        for level in range(depth + 1):
            lo_id = (1 << level) - 1
            order = np.argsort(node, kind="stable")
            bounds = np.searchsorted(node[order],
                                     np.arange(lo_id, 2 * lo_id + 2))
            for j in range(1 << level):
                h = lo_id + j
                sel = order[bounds[j]:bounds[j + 1]]
                c = (np.bincount(yt[sel], minlength=n_classes) if sel.size
                     else counts_of[(h - 1) // 2])
                counts_of[h] = c
                if level == depth:
                    leaf[t, j] = c
                    continue
                best = (_best_split(xt[sel], yt[sel], sorted(set(left_over)),
                                    n_classes, candidates, rng)
                        if sel.size >= 2 else None)
                if best is None:
                    f = left_over[0]
                    thr = np.float32(gmin[f] + rng.random()
                                     * (gmax[f] - gmin[f]))
                else:
                    f, thr, _ = best
                left_over.remove(f)
                feat[t, h], thresh[t, h] = f, thr
            if level < depth:
                f = feat[t, node]
                node = 2 * node + 1 + (xt[np.arange(n), f]
                                       > thresh[t, node])
    return dict(feat=feat, thresh=thresh, leaf=leaf)


def leaf_index(forest: dict, x: np.ndarray) -> np.ndarray:
    """(T, N) leaf reached by each row in each tree; ``x > thresh`` goes
    right, as in the program's trees."""
    feat, thresh = forest["feat"], forest["thresh"]
    n_trees, n_nodes = feat.shape
    depth = int(np.log2(n_nodes + 1))
    x = np.asarray(x)
    rows = np.arange(x.shape[0])
    out = np.empty((n_trees, x.shape[0]), np.int64)
    for t in range(n_trees):
        node = np.zeros(x.shape[0], np.int64)
        for _ in range(depth):
            node = 2 * node + 1 + (x[rows, feat[t, node]] > thresh[t, node])
        out[t] = node - n_nodes
    return out


def vote(forest: dict, x: np.ndarray) -> tuple:
    """Switch semantics: each tree votes its leaf's majority class (lowest
    class on a tie), the most-voted class wins (lowest on a tie), and the
    confidence is the winner's votes over the tree count, in float32.
    -> (pred (N,) int32, conf (N,) float32)."""
    leaves = leaf_index(forest, x)
    n_trees = leaves.shape[0]
    n_classes = forest["leaf"].shape[2]
    cls = np.stack([forest["leaf"][t][leaves[t]].argmax(axis=1)
                    for t in range(n_trees)])              # (T, N)
    votes = np.stack([(cls == c).sum(axis=0) for c in range(n_classes)],
                     axis=1)                               # (N, C)
    pred = votes.argmax(axis=1).astype(np.int32)
    conf = (votes.max(axis=1).astype(np.float32) / np.float32(n_trees))
    return pred, conf


def proba_margin(forest: dict, x: np.ndarray) -> tuple:
    """Backend semantics: the class of highest mean leaf distribution
    (lowest class on a tie), with the gap between the two best mean
    probabilities, in float64. -> (pred (N,) int32, margin (N,))."""
    leaves = leaf_index(forest, x)
    acc = 0.0
    for t in range(leaves.shape[0]):
        counts = forest["leaf"][t][leaves[t]].astype(np.float64)
        acc = acc + counts / np.maximum(counts.sum(axis=1, keepdims=True),
                                        1e-9)
    p = acc / leaves.shape[0]
    srt = np.sort(p, axis=1)
    return p.argmax(axis=1).astype(np.int32), srt[:, -1] - srt[:, -2]
