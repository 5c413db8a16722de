"""Plain reference of the per-request hybrid classifier (numpy, host).

Semantics, written without the program's code: the switch forest votes
on each row of a call; a row whose confidence is below ``tau`` goes to
the backend forest, at most ``capacity`` rows per call, lowest rows
first; every other row keeps the switch answer. ``dtype`` is the
precision the rows are held in: float32 is the configuration's, a lower
one is the control.
"""

from __future__ import annotations

import numpy as np

from chipbench import forest as fo


def pool_answers(pool: np.ndarray, switch: dict, backend: dict,
                 dtype=np.float32) -> dict:
    """Per-row switch and backend answers over the whole row pool (rows
    are independent; which rows reach the backend depends on the call)."""
    x = np.asarray(pool).astype(dtype).astype(np.float32)
    sw, conf = fo.vote(switch, x)
    be, margin = fo.proba_margin(backend, x)
    return dict(sw=sw, conf=conf, be=be, tie=margin < 1e-5)


def call_answer(ans: dict, rows: np.ndarray, cfg: dict) -> tuple:
    """-> (pred, tie) for one call over pool rows ``rows``."""
    fwd = ans["conf"][rows] < np.float32(cfg["threshold"])
    sent = fwd & (np.cumsum(fwd) <= cfg["capacity"])
    pred = np.where(sent, ans["be"][rows], ans["sw"][rows])
    return pred.astype(np.int32), sent & ans["tie"][rows]
