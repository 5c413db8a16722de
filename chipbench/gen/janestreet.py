"""Rows shaped like the Jane Street Market Prediction data (Kaggle 2020-21).

A copy of the program's ``data.janestreet_like.make_janestreet_like``, kept
with the benchmark so that the yardstick does not move when the program's
generator is edited: 130 correlated features from a 12-factor panel plus
noise, a weak nonlinear signal on a sparse subset that includes the
paper's five switch features, and a ~13.1 % positive class.
"""

from __future__ import annotations

import numpy as np

N_FEATURES = 130
SWITCH_FEATURES = [42, 43, 45, 124, 126]      # the paper's section 7.2


def make_rows(n: int, seed, positive_frac: float = 0.131) -> tuple:
    """-> (x (n, 130) float32, y (n,) int32)."""
    rng = np.random.default_rng(seed)
    k = 12
    loadings = rng.normal(0, 1, (k, N_FEATURES))
    factors = rng.normal(0, 1, (n, k))
    x = factors @ loadings + rng.normal(0, 1.5, (n, N_FEATURES))
    sig_idx = np.array(SWITCH_FEATURES + [7, 13, 64, 99])
    s = x[:, sig_idx]
    score = (0.9 * s[:, 0] - 0.7 * s[:, 1] + 0.5 * np.tanh(s[:, 2])
             + 0.6 * s[:, 3] * (s[:, 4] > 0) + 0.3 * s[:, 5]
             - 0.4 * np.abs(s[:, 6]) + 0.25 * s[:, 7] * s[:, 8])
    score = score + rng.normal(0, 2.6, n)
    thr = np.quantile(score, 1.0 - positive_frac)
    return x.astype(np.float32), (score > thr).astype(np.int32)
