"""Histogram-based tree learners with fixed-shape, jit-compatible training.

All trees are *complete* binary trees of a fixed ``max_depth`` stored as flat
heap arrays, which keeps every shape static (level-wise growth, the
XGBoost/LightGBM histogram method). A node that should not split gets the
sentinel threshold ``+inf`` so every sample routes left and the right subtree
becomes unreachable.

Layout (per tree):
  feat   : (2**D - 1,) int32   feature index per internal heap node
  thresh : (2**D - 1,) float32 ``x <= thresh`` routes left; +inf = no split
  leaf   : (2**D, C)   float32 leaf payload (class counts, boosting weight,
                               or isolation sample count)

The IIsy mapping tool (repro.core.mapping) consumes exactly these arrays.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TreeEnsemble:
    """A bag of complete trees plus ensemble metadata."""

    feat: jax.Array        # (T, 2**D - 1) int32
    thresh: jax.Array      # (T, 2**D - 1) float32
    leaf: jax.Array        # (T, 2**D, C) float32
    kind: str = dataclasses.field(metadata=dict(static=True), default="rf")
    # 'dt' | 'rf' | 'xgb' | 'iforest'
    base_score: float = dataclasses.field(metadata=dict(static=True), default=0.0)
    learning_rate: float = dataclasses.field(metadata=dict(static=True), default=1.0)
    n_classes: int = dataclasses.field(metadata=dict(static=True), default=2)

    @property
    def n_trees(self) -> int:
        return self.feat.shape[0]

    @property
    def depth(self) -> int:
        return int(np.log2(self.feat.shape[1] + 1))


# ---------------------------------------------------------------------------
# binning
# ---------------------------------------------------------------------------

def quantile_bin_edges(x: jax.Array, n_bins: int) -> jax.Array:
    """Per-feature quantile bin edges. Returns (F, n_bins - 1).

    ``bin(v) = sum(v > edges)`` so the split rule ``bin <= b`` is exactly
    ``v <= edges[b]``.
    """
    qs = jnp.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = jnp.quantile(x, qs, axis=0).T  # (F, n_bins-1)
    # Strictly increasing edges are not required; duplicated edges simply
    # produce empty bins, which the split search masks out.
    return edges


def bin_data(x: jax.Array, edges: jax.Array) -> jax.Array:
    """Map raw features (N, F) onto bin ids (N, F) in [0, n_bins)."""
    return jnp.sum(x[:, :, None] > edges[None, :, :], axis=2).astype(jnp.int32)


# ---------------------------------------------------------------------------
# shared level-wise growth
# ---------------------------------------------------------------------------

def _grow_level_hist(bins, node_id, stats, n_nodes, n_feat, n_bins):
    """Scatter-add per-(node, feature, bin) statistic histograms.

    bins    : (N, F) int32
    node_id : (N,) int32 current heap-node-within-level index in [0, n_nodes)
    stats   : (N, S) float32 per-sample statistics (class one-hot or (g, h))
    returns : (n_nodes, F, n_bins, S)
    """
    n, f = bins.shape
    flat = (node_id[:, None] * n_feat + jnp.arange(n_feat)[None, :]) * n_bins + bins
    hist = jnp.zeros((n_nodes * n_feat * n_bins, stats.shape[1]), stats.dtype)
    hist = hist.at[flat].add(stats[:, None, :])
    return hist.reshape(n_nodes, n_feat, n_bins, stats.shape[1])


def _route(bins, node_id, level_feat, level_split_bin):
    """Advance samples one level down. Returns node index within next level."""
    f = level_feat[node_id]                       # (N,)
    b = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0]
    go_right = b > level_split_bin[node_id]
    return node_id * 2 + go_right.astype(jnp.int32)


def _gini_best_split(hist, min_leaf):
    """Best (feature, bin) per node from class-count histograms.

    hist: (nodes, F, B, C) counts. Returns (feat, split_bin, has_split).
    """
    left = jnp.cumsum(hist, axis=2)                     # counts left of split
    total = left[:, :, -1:, :]
    right = total - left
    n_l = left.sum(-1)                                  # (nodes, F, B)
    n_r = right.sum(-1)
    n_t = n_l + n_r

    def gini(counts, n):
        p = counts / jnp.maximum(n[..., None], 1.0)
        return 1.0 - jnp.sum(p * p, axis=-1)

    g_parent = gini(total, n_t[..., -1:])               # (nodes, F, 1)
    gain = g_parent - (n_l / jnp.maximum(n_t, 1.0)) * gini(left, n_l) \
                    - (n_r / jnp.maximum(n_t, 1.0)) * gini(right, n_r)
    valid = (n_l >= min_leaf) & (n_r >= min_leaf)
    valid = valid.at[:, :, -1].set(False)               # right side empty
    gain = jnp.where(valid, gain, NEG_INF)
    flat = gain.reshape(gain.shape[0], -1)
    best = jnp.argmax(flat, axis=1)
    n_bins = hist.shape[2]
    return best // n_bins, best % n_bins, jnp.max(flat, axis=1) > 0.0


def _xgb_best_split(hist, reg_lambda, min_child_weight, gamma=0.0):
    """Best split from (g, h) histograms. hist: (nodes, F, B, 2).

    ``gamma`` is XGBoost's min-split-gain: weak splits are pruned, which
    is the paper's §4.2 "prune trees to create action codes of feasible
    length" knob (fewer thresholds -> smaller decision tables)."""
    left = jnp.cumsum(hist, axis=2)
    total = left[:, :, -1:, :]
    right = total - left
    gl, hl = left[..., 0], left[..., 1]
    gr, hr = right[..., 0], right[..., 1]
    gt, ht = total[..., 0], total[..., 1]

    def score(g, h):
        return (g * g) / (h + reg_lambda)

    gain = 0.5 * (score(gl, hl) + score(gr, hr) - score(gt, ht))
    valid = (hl >= min_child_weight) & (hr >= min_child_weight)
    valid = valid.at[:, :, -1].set(False)
    gain = jnp.where(valid, gain, NEG_INF)
    flat = gain.reshape(gain.shape[0], -1)
    best = jnp.argmax(flat, axis=1)
    n_bins = hist.shape[2]
    return best // n_bins, best % n_bins, jnp.max(flat, axis=1) > gamma


def _fill_level(feat_heap, thresh_heap, level, level_feat, level_thresh):
    start = (1 << level) - 1
    feat_heap = jax.lax.dynamic_update_slice(feat_heap, level_feat, (start,))
    thresh_heap = jax.lax.dynamic_update_slice(thresh_heap, level_thresh, (start,))
    return feat_heap, thresh_heap


# ---------------------------------------------------------------------------
# decision tree / random forest
# ---------------------------------------------------------------------------

def _fit_one_gini_tree(bins, y1h, edges, depth, n_bins, min_leaf, feat_mask):
    """Grow one gini tree on pre-binned data. All shapes static.

    bins (N, F) int32, y1h (N, C), edges (F, n_bins-1), feat_mask (F,) bool.
    """
    n, n_feat = bins.shape
    n_heap = (1 << depth) - 1
    feat_heap = jnp.zeros((n_heap,), jnp.int32)
    thresh_heap = jnp.full((n_heap,), jnp.inf, jnp.float32)
    node_id = jnp.zeros((n,), jnp.int32)

    for level in range(depth):
        n_nodes = 1 << level
        hist = _grow_level_hist(bins, node_id, y1h, n_nodes, n_feat, n_bins)
        masked = jnp.where(feat_mask[None, :, None, None], hist,
                           jnp.zeros_like(hist))
        bf, bb, ok = _gini_best_split(masked, min_leaf)
        thr = edges[bf, jnp.minimum(bb, edges.shape[1] - 1)]
        level_feat = jnp.where(ok, bf, 0).astype(jnp.int32)
        level_thresh = jnp.where(ok, thr, jnp.inf)
        # route with the *bin* rule (bin <= bb left); unsplit nodes go left
        eff_bin = jnp.where(ok, bb, n_bins)  # everything <= n_bins-1 -> left
        node_id = _route(bins, node_id, level_feat, eff_bin)
        feat_heap, thresh_heap = _fill_level(
            feat_heap, thresh_heap, level, level_feat, level_thresh)

    # leaves: class counts
    n_leaf = 1 << depth
    leaf = jnp.zeros((n_leaf, y1h.shape[1]), jnp.float32).at[node_id].add(y1h)
    return feat_heap, thresh_heap, leaf


def fit_decision_tree(x, y, *, n_classes, max_depth=5, n_bins=64,
                      min_leaf=1.0, edges=None):
    """CART-style gini decision tree. Returns a single-tree TreeEnsemble."""
    x = jnp.asarray(x, jnp.float32)
    y1h = jax.nn.one_hot(jnp.asarray(y), n_classes, dtype=jnp.float32)
    if edges is None:
        edges = quantile_bin_edges(x, n_bins)
    bins = bin_data(x, edges)
    feat_mask = jnp.ones((x.shape[1],), bool)
    f, t, l = jax.jit(_fit_one_gini_tree, static_argnums=(3, 4))(
        bins, y1h, edges, max_depth, n_bins, min_leaf, feat_mask)
    return TreeEnsemble(feat=f[None], thresh=t[None], leaf=l[None],
                        kind="dt", n_classes=n_classes)


def fit_random_forest(x, y, *, n_classes, n_trees=10, max_depth=5, n_bins=64,
                      min_leaf=1.0, max_features=None, seed=0,
                      tree_chunk=16, edges=None):
    """Bagged gini trees (bootstrap rows + per-tree feature subsampling)."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y)
    n, n_feat = x.shape
    if max_features is None:
        max_features = max(1, int(np.sqrt(n_feat)))
    if edges is None:
        edges = quantile_bin_edges(x, n_bins)
    bins = bin_data(x, edges)
    y1h = jax.nn.one_hot(y, n_classes, dtype=jnp.float32)

    def one_tree(key):
        k_boot, k_feat = jax.random.split(key)
        idx = jax.random.randint(k_boot, (n,), 0, n)
        perm = jax.random.permutation(k_feat, n_feat)
        mask = jnp.zeros((n_feat,), bool).at[perm[:max_features]].set(True)
        return _fit_one_gini_tree(bins[idx], y1h[idx], edges,
                                  max_depth, n_bins, min_leaf, mask)

    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    fit_chunk = jax.jit(jax.vmap(one_tree))
    outs = [fit_chunk(keys[i:i + tree_chunk])
            for i in range(0, n_trees, tree_chunk)]
    f, t, l = (jnp.concatenate([o[j] for o in outs]) for j in range(3))
    return TreeEnsemble(feat=f, thresh=t, leaf=l, kind="rf",
                        n_classes=n_classes)


# ---------------------------------------------------------------------------
# XGBoost-style boosting (binary logistic)
# ---------------------------------------------------------------------------

def _fit_one_xgb_tree(bins, g, h, edges, depth, n_bins, reg_lambda,
                      min_child_weight, gamma=0.0):
    n, n_feat = bins.shape
    n_heap = (1 << depth) - 1
    feat_heap = jnp.zeros((n_heap,), jnp.int32)
    thresh_heap = jnp.full((n_heap,), jnp.inf, jnp.float32)
    node_id = jnp.zeros((n,), jnp.int32)
    stats = jnp.stack([g, h], axis=1)

    for level in range(depth):
        n_nodes = 1 << level
        hist = _grow_level_hist(bins, node_id, stats, n_nodes, n_feat, n_bins)
        bf, bb, ok = _xgb_best_split(hist, reg_lambda, min_child_weight,
                                     gamma)
        thr = edges[bf, jnp.minimum(bb, edges.shape[1] - 1)]
        level_feat = jnp.where(ok, bf, 0).astype(jnp.int32)
        level_thresh = jnp.where(ok, thr, jnp.inf)
        eff_bin = jnp.where(ok, bb, n_bins)
        node_id = _route(bins, node_id, level_feat, eff_bin)
        feat_heap, thresh_heap = _fill_level(
            feat_heap, thresh_heap, level, level_feat, level_thresh)

    n_leaf = 1 << depth
    g_leaf = jnp.zeros((n_leaf,), jnp.float32).at[node_id].add(g)
    h_leaf = jnp.zeros((n_leaf,), jnp.float32).at[node_id].add(h)
    w = -g_leaf / (h_leaf + reg_lambda)
    return feat_heap, thresh_heap, w[:, None], node_id


def fit_xgboost(x, y, *, n_trees=10, max_depth=4, n_bins=64,
                learning_rate=0.3, reg_lambda=1.0, min_child_weight=1.0,
                gamma=0.0, base_score=0.0, edges=None):
    """Second-order boosted trees, binary logistic objective."""
    x = jnp.asarray(x, jnp.float32)
    yf = jnp.asarray(y, jnp.float32)
    if edges is None:
        edges = quantile_bin_edges(x, n_bins)
    bins = bin_data(x, edges)

    fit_tree = jax.jit(_fit_one_xgb_tree, static_argnums=(4, 5))

    margin = jnp.full((x.shape[0],), base_score, jnp.float32)
    feats, threshs, leaves = [], [], []
    for _ in range(n_trees):
        p = jax.nn.sigmoid(margin)
        g = p - yf
        h = jnp.maximum(p * (1.0 - p), 1e-6)
        f, t, w, node_id = fit_tree(bins, g, h, edges, max_depth, n_bins,
                                    reg_lambda, min_child_weight, gamma)
        margin = margin + learning_rate * w[node_id, 0]
        feats.append(f); threshs.append(t); leaves.append(w)
    return TreeEnsemble(feat=jnp.stack(feats), thresh=jnp.stack(threshs),
                        leaf=jnp.stack(leaves), kind="xgb",
                        base_score=base_score, learning_rate=learning_rate,
                        n_classes=2)


# ---------------------------------------------------------------------------
# Isolation forest
# ---------------------------------------------------------------------------

def _fit_one_iso_tree(bins, edges, depth, n_bins, key):
    n, n_feat = bins.shape
    n_heap = (1 << depth) - 1
    feat_heap = jnp.zeros((n_heap,), jnp.int32)
    thresh_heap = jnp.full((n_heap,), jnp.inf, jnp.float32)
    node_id = jnp.zeros((n,), jnp.int32)
    ones = jnp.ones((n, 1), jnp.float32)

    for level in range(depth):
        n_nodes = 1 << level
        key, k_f, k_b = jax.random.split(key, 3)
        hist = _grow_level_hist(bins, node_id, ones, n_nodes, n_feat,
                                n_bins)[..., 0]               # (nodes, F, B)
        level_feat = jax.random.randint(k_f, (n_nodes,), 0, n_feat)
        h_f = jnp.take_along_axis(
            hist, level_feat[:, None, None], axis=1)[:, 0, :]  # (nodes, B)
        present = h_f > 0
        lo = jnp.argmax(present, axis=1)
        hi = n_bins - 1 - jnp.argmax(present[:, ::-1], axis=1)
        u = jax.random.uniform(k_b, (n_nodes,))
        bb = (lo + (u * jnp.maximum(hi - lo, 0)).astype(jnp.int32))
        bb = jnp.clip(bb, 0, n_bins - 2)
        splittable = hi > lo
        thr = edges[level_feat, jnp.minimum(bb, edges.shape[1] - 1)]
        level_thresh = jnp.where(splittable, thr, jnp.inf)
        eff_bin = jnp.where(splittable, bb, n_bins)
        node_id = _route(bins, node_id, jnp.where(splittable, level_feat, 0),
                         eff_bin)
        feat_heap, thresh_heap = _fill_level(
            feat_heap, thresh_heap, level,
            jnp.where(splittable, level_feat, 0).astype(jnp.int32),
            level_thresh)

    n_leaf = 1 << depth
    count = jnp.zeros((n_leaf, 1), jnp.float32).at[node_id].add(ones)
    return feat_heap, thresh_heap, count


def fit_isolation_forest(x, *, n_trees=32, max_depth=6, n_bins=64,
                         subsample=256, seed=0, edges=None):
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    if edges is None:
        edges = quantile_bin_edges(x, n_bins)
    bins_full = bin_data(x, edges)
    sub = min(subsample, n)

    def one_tree(key):
        k_s, k_t = jax.random.split(key)
        idx = jax.random.choice(k_s, n, (sub,), replace=False)
        return _fit_one_iso_tree(bins_full[idx], edges, max_depth, n_bins, k_t)

    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    f, t, l = jax.jit(jax.vmap(one_tree))(keys)
    return TreeEnsemble(feat=f, thresh=t, leaf=l, kind="iforest", n_classes=2)


# ---------------------------------------------------------------------------
# prediction
# ---------------------------------------------------------------------------

# Deepest tree that ``tree_leaf_indices`` walks level by level with selects;
# deeper trees take the heap walk. On a TPU v5e (32 trees, 1,024 rows) the
# selects took 41, 126 and 852 us of device time at depths 8, 10 and 12,
# the heap walk 6.9, 8.7 and 10.5 ms: 12 is the deepest depth measured,
# and the selects still won there. With 60 trees on 130 features (depths
# 6 and 8, 1,024 rows) they won 160x and 149x: the value pick's growth
# with the features does not move the choice.
SELECT_MAX_DEPTH = 12


def _selects(depth: int) -> bool:
    """Whether trees ``depth`` deep take the level-wise selects, for the
    walk and the leaf read alike."""
    return depth <= SELECT_MAX_DEPTH


def _leaf_index(feat, thresh, x, depth):
    """Heap walk, fixed depth. x: (N, F); feat/thresh: (H,). -> (N,) leaf id.

    Three row-dependent gathers per level (node feature, node threshold,
    the row's value), which a TPU runs nearly element by element; the
    work grows with ``depth``. The path for trees deeper than
    ``SELECT_MAX_DEPTH``."""
    n = x.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    for _ in range(depth):
        f = feat[node]
        t = thresh[node]
        xv = jnp.take_along_axis(x, f[:, None], axis=1)[:, 0]
        node = 2 * node + 1 + (xv > t).astype(jnp.int32)
    return node - ((1 << depth) - 1)


def _pick(hit, table):
    """``sum_k where(hit[k], table[k], 0)`` over the leading axis, added on
    the int32 bits of ``table``: where at most one ``k`` hits, the value
    picked comes back bit for bit (-0.0, +-inf, NaN and subnormals too)
    on any backend; where none hits, 0."""
    if table.dtype == jnp.int32:
        return jnp.sum(jnp.where(hit, table, 0), axis=0)
    bits = jax.lax.bitcast_convert_type(table, jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(hit, bits, 0), axis=0), table.dtype)


# Elements of the widest select intermediate per block of rows. A TPU
# fuses each pick into its reduction and holds none of it; XLA's CPU
# backend writes it out whole, so large batches go in blocks of rows.
# The blocks engage on every platform: on a TPU the 32-tree leaf pick
# runs in a ``while`` loop from depth 10 at 1,024 rows.
_BLOCK_ELEMS = 1 << 25


def _in_row_blocks(fn, a, width):
    """``fn(a)`` for ``a`` with rows on axis 0 and a result with rows on
    axis 1, run over blocks of rows (``lax.map``) where ``width`` elements
    per row would put more than ``_BLOCK_ELEMS`` in one intermediate."""
    n = a.shape[0]
    rows = max(128, (_BLOCK_ELEMS // width) & -128)
    if n <= rows:
        return fn(a)
    nb = -(-n // rows)
    pad = [(0, nb * rows - n)] + [(0, 0)] * (a.ndim - 1)
    out = jax.lax.map(fn, jnp.pad(a, pad).reshape(nb, rows, *a.shape[1:]))
    out = jnp.moveaxis(out, 0, 1)                       # (T, nb, rows, ...)
    return out.reshape(out.shape[0], nb * rows, *out.shape[3:])[:, :n]


@jax.jit
def _level_select(feat, thresh, x):
    """Level-wise walk with no gather. feat/thresh (T, H), x (N, F) ->
    (T, N) leaf id.

    Each (tree, row) holds its node ``j`` within the level; the node's
    feature and threshold are one-hot picks over the level's static node
    set, the row's value a one-hot pick over the F features, so the
    compare is the heap walk's bit for bit. Rows stay on the minor axis
    and the widest intermediate is (max(2**(D-1), F), T, N), never the
    whole tree. Work grows with 2**depth and with F."""
    depth = (feat.shape[1] + 1).bit_length() - 1
    xt = x.T[:, None, :]                                     # (F, 1, N)
    fid = jnp.arange(x.shape[1], dtype=jnp.int32)[:, None, None]
    j = jnp.zeros((feat.shape[0], x.shape[0]), jnp.int32)    # (T, N)
    for level in range(depth):
        lo, w = (1 << level) - 1, 1 << level
        at = j[None] == jnp.arange(w, dtype=jnp.int32)[:, None, None]
        f = _pick(at, feat[:, lo:lo + w].T[:, :, None])      # (T, N)
        t = _pick(at, thresh[:, lo:lo + w].T[:, :, None])
        xv = _pick(f[None] == fid, xt)
        j = 2 * j + (xv > t).astype(jnp.int32)
    return j


def tree_leaf_indices(ens: TreeEnsemble, x) -> jax.Array:
    """(T, N) leaf index per tree.

    Trees up to ``SELECT_MAX_DEPTH`` deep take ``_level_select`` (about
    2**depth + depth * F selects per tree and row, no gather); deeper
    trees take the heap walk ``_leaf_index`` (depth gathers, whose
    per-element cost wins once 2**depth outgrows it). The choice rests on the ensemble's static
    shape alone, and both give the same indices."""
    x = jnp.asarray(x, jnp.float32)
    depth = ens.depth
    if _selects(depth):
        width = max(1 << max(depth - 1, 0), x.shape[1])  # nodes or features
        return _in_row_blocks(partial(_level_select, ens.feat, ens.thresh),
                              x, ens.n_trees * width)
    return jax.vmap(lambda f, t: _leaf_index(f, t, x, depth))(ens.feat,
                                                              ens.thresh)


@jax.jit
def _leaf_pick(leaf, leaf_idx):
    """leaf (T, L, C), leaf_idx (T, N) -> (T, N, C): a one-hot pick over
    the L leaves."""
    at = leaf_idx[None] == jnp.arange(leaf.shape[1],
                                      dtype=jnp.int32)[:, None, None]
    vals = _pick(at[:, None], jnp.transpose(leaf, (1, 2, 0))[..., None])
    return jnp.moveaxis(vals, 0, -1)                         # (T, N, C)


def _leaf_values(leaf, leaf_idx):
    """leaf (T, L, C), leaf_idx (T, N) -> (T, N, C), each tree's leaf row:
    picked where ``tree_leaf_indices`` takes the selects, gathered where
    it takes the heap walk."""
    if not _selects(leaf.shape[1].bit_length() - 1):
        return jnp.take_along_axis(leaf, leaf_idx[:, :, None], axis=1)
    return _in_row_blocks(lambda i: _leaf_pick(leaf, i.T), leaf_idx.T,
                          leaf.size)


def predict_proba_tree_ensemble(ens: TreeEnsemble, x) -> jax.Array:
    """Mean per-tree class distribution (DT/RF). -> (N, C)."""
    leaf_idx = tree_leaf_indices(ens, x)               # (T, N)
    counts = _leaf_values(ens.leaf, leaf_idx)          # (T, N, C)
    probs = counts / jnp.maximum(counts.sum(-1, keepdims=True), 1e-9)
    return probs.mean(axis=0)


def predict_margin_xgboost(ens: TreeEnsemble, x) -> jax.Array:
    leaf_idx = tree_leaf_indices(ens, x)
    w = _leaf_values(ens.leaf, leaf_idx)[..., 0]                  # (T, N)
    return ens.base_score + ens.learning_rate * w.sum(axis=0)


def _c_factor(n):
    n = jnp.maximum(n, 2.0)
    return 2.0 * (jnp.log(n - 1.0) + 0.5772156649) - 2.0 * (n - 1.0) / n


def predict_iforest_score(ens: TreeEnsemble, x, subsample=256) -> jax.Array:
    """Anomaly score in (0, 1); higher = more anomalous."""
    leaf_idx = tree_leaf_indices(ens, x)
    size = _leaf_values(ens.leaf, leaf_idx)[..., 0]
    depth = ens.depth
    path = depth + jnp.where(size > 1, _c_factor(size), 0.0)
    e_path = path.mean(axis=0)
    return 2.0 ** (-e_path / _c_factor(jnp.float32(subsample)))


def predict_tree_ensemble(ens: TreeEnsemble, x) -> jax.Array:
    """Hard class prediction for any tree kind."""
    if ens.kind in ("dt", "rf"):
        return jnp.argmax(predict_proba_tree_ensemble(ens, x), axis=1)
    if ens.kind == "xgb":
        return (predict_margin_xgboost(ens, x) > 0.0).astype(jnp.int32)
    if ens.kind == "iforest":
        return (predict_iforest_score(ens, x) > 0.5).astype(jnp.int32)
    raise ValueError(ens.kind)
