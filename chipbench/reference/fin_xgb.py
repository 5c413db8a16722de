"""Plain reference of the finance deployment with its XGBoost backend
(numpy, host).

Semantics, written without the program's code: the switch forest votes
on each row's switch columns; a row whose confidence is below ``tau``
goes, whole, to the backend, at most ``capacity`` rows per call, lowest
rows first (``fin_batch.call_answer``); every other row keeps the switch
answer. The backend walks each heap tree with float32 compares (``x >
thresh`` goes right), sums ``learning_rate`` times the leaf weights over
the trees in float64, adds ``base_score``, and answers 1 where that
margin is above 0. ``dtype`` is the precision the rows are held in:
float32 is the configuration's, a lower one is the control.
"""

from __future__ import annotations

import numpy as np

from chipbench import forest as fo
from chipbench.reference.fin_batch import call_answer

__all__ = ["call_answer", "margin_band", "pool_answers"]

# the float32 unit roundoff
U32 = 2.0 ** -24


def margin_band(backend: dict, x: np.ndarray,
                trees_per_pass: int = 50) -> tuple:
    """-> (margin (N,) float64, band (N,)). The program sums the T leaf
    weights in float32, in an order of its compiler's choosing: each of
    the T - 1 additions, and the product with the learning rate, rounds
    by at most U32 of a value no larger than sum_t |lr * w_t|, so its
    margin lies within ``band = T * U32 * sum_t |lr * w_t|`` of the exact
    one, and a margin within the band may take either sign there."""
    x = np.asarray(x, np.float32)
    lr = float(backend["learning_rate"])
    n_trees = backend["feat"].shape[0]
    total = np.zeros(x.shape[0])
    size = np.zeros(x.shape[0])
    for lo in range(0, n_trees, trees_per_pass):
        part = slice(lo, lo + trees_per_pass)
        leaves = fo.leaf_index({"feat": backend["feat"][part],
                                "thresh": backend["thresh"][part]}, x)
        w = lr * np.take_along_axis(backend["leaf"][part], leaves,
                                    axis=1).astype(np.float64)
        total += w.sum(axis=0)
        size += np.abs(w).sum(axis=0)
    return backend["base_score"] + total, n_trees * U32 * size


def pool_answers(pool: np.ndarray, switch: dict, backend: dict,
                 switch_features, dtype=np.float32) -> dict:
    """Per-row switch and backend answers over the whole row pool (rows
    are independent; which rows reach the backend depends on the call)."""
    x = np.asarray(pool).astype(dtype).astype(np.float32)
    sw, conf = fo.vote(switch, x[:, list(switch_features)])
    margin, band = margin_band(backend, x)
    return dict(sw=sw, conf=conf, be=(margin > 0).astype(np.int32),
                tie=np.abs(margin) <= band)
