"""Fully grown extremely randomized trees, made by the benchmark in numpy.

The forest scikit-learn's ``ExtraTreesClassifier`` fits at its documented
defaults (``criterion='gini'``, ``max_depth=None``,
``min_samples_split=2``, ``min_samples_leaf=1``, ``max_features='sqrt'``,
``bootstrap=False``; Geurts et al., Machine Learning 2006), written without
scikit-learn so that the benchmark's model depends on nothing the program
computes. Every tree sees every training row. A node with at least two
rows of more than one class splits; any other is a leaf. It draws
``max_features`` features without replacement, in a random order, and
where none of them varies over its rows draws on until one does (a leaf
where none does); for each feature that varies it draws one threshold
uniformly between the rows' smallest and largest value, and keeps the
split of lowest weighted Gini impurity, the first drawn on a tie.

A threshold is drawn in float64, as scikit-learn draws it, and stored as
the largest float32 not above it, so that ``x <= t`` on float32 rows
decides as the float64 compare does. A feature is constant in a node
where its largest value exceeds its smallest by at most 1e-7, as in
scikit-learn. scikit-learn also skips, without counting them as drawn,
the features an ancestor found constant; on the continuous rows of
``gen/janestreet.py`` no feature is constant in a node that can split,
and the maker does not track them.

The trees come out as node arrays (``repro.ml.trees``' layout): nodes
numbered breadth first, each split's children side by side (``left`` and
``left + 1``), a leaf holding its own index with threshold +inf, and each
node's class counts. Trees grow level by level in fixed groups of
``TREES_PER_PASS``, all the nodes of a group's level in one vectorised
pass; groups run on threads, each from its own seed, so the forest does
not depend on how many threads ran it.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FEATURE_THRESHOLD = 1e-7
# trees grown together in one vectorised pass; part of the forest's
# definition, since each group draws from its own seed
TREES_PER_PASS = 4


def _f32_at_most(t: np.ndarray) -> np.ndarray:
    """The largest float32 not greater than each float64 ``t``."""
    r = t.astype(np.float32)
    return np.where(r.astype(np.float64) > t,
                    np.nextafter(r, np.float32(-np.inf)), r)


def _draw(rng, n_groups: int, n_feat: int, k: int) -> np.ndarray:
    """(n_groups, k) features, each row ``k`` distinct ones in a random
    order (Floyd's subset, then shuffled)."""
    out = np.empty((n_groups, k), np.int32)
    for i, j in enumerate(range(n_feat - k, n_feat)):
        t = rng.integers(0, j + 1, n_groups)
        taken = (out[:, :i] == t[:, None]).any(axis=1)
        out[:, i] = np.where(taken, j, t)
    return np.take_along_axis(out, rng.random((n_groups, k)).argsort(axis=1),
                              axis=1)


def _threshold(rng, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """One uniform draw in [lo, hi) per entry, in float64; a draw that
    rounds to ``hi`` takes ``lo`` (scikit-learn's rule). -> float32."""
    lo, hi = lo.astype(np.float64), hi.astype(np.float64)
    t = lo + (hi - lo) * rng.random(lo.shape)
    return _f32_at_most(np.where(t == hi, lo, t))


def _fallback(x, rows, drawn, rng) -> tuple:
    """For a node none of whose ``drawn`` features varies: the first of
    the other features, in a random order, that does, with its
    threshold; (-1, inf) where none does."""
    for f in rng.permutation(np.setdiff1d(np.arange(x.shape[1]), drawn)):
        v = x[rows, f]
        lo, hi = v.min(), v.max()
        if hi > np.float64(lo) + FEATURE_THRESHOLD:
            return int(f), _threshold(rng, np.array([lo]), np.array([hi]))[0]
    return -1, np.float32(np.inf)


def _grow_group(x, y, n_trees: int, max_features: int, n_classes: int,
                rng) -> tuple:
    """Grow ``n_trees`` trees on all of ``x`` (N, F), level by level.
    -> (nodes per tree, depth, node records)."""
    n, n_feat = x.shape
    k = min(max_features, n_feat)
    xs = x.reshape(-1)
    rows = np.tile(np.arange(n, dtype=np.int32), n_trees)   # by group
    g_tree = np.arange(n_trees)
    g_node = np.zeros(n_trees, np.int64)
    g_len = np.full(n_trees, n, np.int64)
    next_id = np.ones(n_trees, np.int64)
    recs = []
    level = 0
    while True:
        n_groups = g_len.size
        gi = np.repeat(np.arange(n_groups), g_len)
        counts = np.bincount(gi * n_classes + y[rows],
                             minlength=n_groups * n_classes
                             ).reshape(n_groups, n_classes)
        can = (g_len >= 2) & (counts.max(axis=1) < g_len)
        if not can.any():
            recs.append((g_tree, g_node, np.zeros(n_groups, np.int32),
                         np.full(n_groups, np.inf, np.float32), g_node,
                         counts))
            return next_id, level, recs

        # the nodes that may split, their rows contiguous in ``rs``
        s_len = g_len[can]
        s = s_len.size
        keep = np.repeat(can, g_len)
        rs, ys = rows[keep], y[rows[keep]]
        s_start = np.concatenate([[0], np.cumsum(s_len)[:-1]])
        si = np.repeat(np.arange(s), s_len)
        feats = _draw(rng, s, n_feat, k).astype(np.int32)                    # (S, k)
        v = np.take(xs, (rs * n_feat)[:, None]
                    + np.repeat(feats, s_len, axis=0))      # (M, k)
        lo = np.minimum.reduceat(v, s_start, axis=0)
        hi = np.maximum.reduceat(v, s_start, axis=0)
        varies = hi > lo.astype(np.float64) + FEATURE_THRESHOLD
        thr = _threshold(rng, lo, hi)                       # (S, k)
        go_left = v <= np.repeat(thr, s_len, axis=0)
        n_l = np.add.reduceat(go_left, s_start, axis=0, dtype=np.int32)
        n_r = s_len[:, None] - n_l
        score = np.zeros(n_l.shape)
        for c in range(n_classes):
            c_l = np.add.reduceat(go_left & (ys == c)[:, None], s_start,
                                  axis=0, dtype=np.int32)
            c_r = counts[can, c][:, None] - c_l
            score += (c_l * c_l) / np.maximum(n_l, 1) \
                + (c_r * c_r) / np.maximum(n_r, 1)
        score[~varies] = -np.inf
        best = score.argmax(axis=1)
        b_feat = feats[np.arange(s), best]
        b_thr = thr[np.arange(s), best]
        for j in np.flatnonzero(~varies.any(axis=1)):
            b_feat[j], b_thr[j] = _fallback(
                x, rs[s_start[j]:s_start[j] + s_len[j]], feats[j], rng)

        # children: the nodes that split, their rows partitioned in place
        # (left rows first, each side in its old order)
        split = b_feat >= 0
        left = x[rs, np.maximum(b_feat, 0)[si]] <= b_thr[si]
        cum = np.concatenate([[0], np.cumsum(left)])
        n_left = cum[s_start + s_len] - cum[s_start]
        rank_l = cum[:-1] - cum[s_start][si]
        rank_r = np.arange(rs.size) - s_start[si] - rank_l
        pos = s_start[si] + np.where(left, rank_l, n_left[si] + rank_r)
        new_rows = np.empty_like(rs)
        new_rows[pos] = rs

        s_tree = g_tree[can][split]
        per_tree = np.bincount(s_tree, minlength=n_trees)
        rank = np.arange(s_tree.size) - (np.cumsum(per_tree)
                                         - per_tree)[s_tree]
        child = next_id[s_tree] + 2 * rank
        next_id += 2 * per_tree

        node_feat = np.zeros(n_groups, np.int32)
        node_thr = np.full(n_groups, np.inf, np.float32)
        node_left = g_node.copy()
        idx = np.flatnonzero(can)[split]
        node_feat[idx], node_thr[idx], node_left[idx] = (
            b_feat[split], b_thr[split], child)
        recs.append((g_tree, g_node, node_feat, node_thr, node_left, counts))

        sel = np.repeat(split, s_len)
        rows = new_rows[sel]
        g_len = np.stack([n_left[split], (s_len - n_left)[split]],
                         axis=1).ravel()
        g_tree = np.repeat(s_tree, 2)
        g_node = np.stack([child, child + 1], axis=1).ravel()
        level += 1


def grow_forest(x: np.ndarray, y: np.ndarray, *, n_trees: int,
                max_features: int, n_classes: int, seed,
                workers: int | None = None) -> dict:
    """-> {"feat", "thresh", "left" (T, M), "leaf" (T, M, C) class
    counts, "depth", "nodes" (T,)}: ``n_trees`` fully grown trees on rows
    ``x`` (N, F) with labels ``y`` in [0, n_classes), ``M`` the largest
    tree's node count (smaller trees padded with leaves), ``depth`` the
    deepest leaf's level. Tree group ``g`` draws from seed ``[*seed,
    g]``."""
    x = np.ascontiguousarray(x, np.float32)
    y = np.asarray(y, np.int64)
    seed = [int(s) for s in np.atleast_1d(seed)]
    groups = [(g, min(TREES_PER_PASS, n_trees - lo))
              for g, lo in enumerate(range(0, n_trees, TREES_PER_PASS))]

    def one(group):
        g, size = group
        return _grow_group(x, y, size, max_features, n_classes,
                           np.random.default_rng(seed + [g]))

    with ThreadPoolExecutor(workers or os.cpu_count() or 1) as pool:
        done = list(pool.map(one, groups))
    nodes = np.concatenate([d[0] for d in done])
    m = int(nodes.max())
    feat = np.zeros((n_trees, m), np.int32)
    thresh = np.full((n_trees, m), np.inf, np.float32)
    left = np.tile(np.arange(m, dtype=np.int32), (n_trees, 1))
    leaf = np.zeros((n_trees, m, n_classes), np.float32)
    for (g, _), (_, _, recs) in zip(groups, done):
        t0 = g * TREES_PER_PASS
        for tree, node, f, th, lt, c in recs:
            t = t0 + tree
            feat[t, node], thresh[t, node], left[t, node] = f, th, lt
            leaf[t, node] = c
    return dict(feat=feat, thresh=thresh, left=left, leaf=leaf,
                depth=max(d[1] for d in done), nodes=nodes)
