"""Plain reference of the finance deployment with its fully grown
extra-trees backend (numpy, host).

Semantics, written without the program's code: the switch forest votes
on each row's switch columns; a row whose confidence is below ``tau``
goes, whole, to the backend, at most ``capacity`` rows per call, lowest
rows first (``fin_batch.call_answer``); every other row keeps the switch
answer. The backend walks each tree's node arrays from the root until
the row reaches a leaf (a node that is its own ``left``), with float32
compares (``x > thresh`` goes to the right child, ``left + 1``), turns
each leaf's class counts into fractions, averages them over the trees in
float64, and answers the class of highest mean (the lowest on a tie). A
backend row whose two best mean fractions lie within 1e-5 of each other
counts either way, as in ``fin_batch``. ``dtype`` is the precision the
rows are held in: float32 is the configuration's, a lower one is the
control.
"""

from __future__ import annotations

import numpy as np

from chipbench import forest as fo
from chipbench.reference.fin_batch import call_answer

__all__ = ["call_answer", "leaf_nodes", "pool_answers", "proba_margin"]


def leaf_nodes(backend: dict, x: np.ndarray) -> np.ndarray:
    """(T, N) leaf node each row reaches in each tree."""
    feat, thresh, left = backend["feat"], backend["thresh"], backend["left"]
    x = np.asarray(x, np.float32)
    out = np.empty((feat.shape[0], x.shape[0]), np.int64)
    for t in range(feat.shape[0]):
        node = np.zeros(x.shape[0], np.int64)
        live = np.arange(x.shape[0])
        while live.size:
            n = node[live]
            inner = left[t, n] != n
            live, n = live[inner], n[inner]
            node[live] = left[t, n] + (x[live, feat[t, n]] > thresh[t, n])
        out[t] = node
    return out


def proba_margin(backend: dict, x: np.ndarray) -> tuple:
    """-> (pred (N,) int32, margin (N,)): the class of highest mean leaf
    fraction and the gap between the two best means, in float64."""
    leaves = leaf_nodes(backend, x)
    acc = 0.0
    for t in range(leaves.shape[0]):
        counts = backend["leaf"][t][leaves[t]].astype(np.float64)
        acc = acc + counts / counts.sum(axis=1, keepdims=True)
    p = acc / leaves.shape[0]
    srt = np.sort(p, axis=1)
    return p.argmax(axis=1).astype(np.int32), srt[:, -1] - srt[:, -2]


def pool_answers(pool: np.ndarray, switch: dict, backend: dict,
                 server: dict, dtype=np.float32) -> dict:
    """Per-row switch answers over the whole row pool, and backend
    answers for the rows the switch may forward (confidence below tau);
    which of those reach the backend depends on the call."""
    x = np.asarray(pool).astype(dtype).astype(np.float32)
    sw, conf = fo.vote(switch, x[:, list(server["switch_features"])])
    low = np.flatnonzero(conf < np.float32(server["threshold"]))
    be = np.zeros(len(x), np.int32)
    tie = np.zeros(len(x), bool)
    be[low], margin = proba_margin(backend, x[low])
    tie[low] = margin < 1e-5
    return dict(sw=sw, conf=conf, be=be, tie=tie)
