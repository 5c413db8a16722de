"""Runs the finance deployment with a fully grown extra-trees backend:
``HybridServer.classify`` on whole 130-feature rows.

As ``fin_xgb`` runs its cells, with three differences. The backend is
scikit-learn's ``ExtraTreesClassifier`` at its defaults, grown by the
benchmark (``grown.py``) from the configuration's ``model_seed`` on the
backend's own ``train_rows``: 100 trees of about 30,000 nodes and 50
levels as node arrays, which the server takes as a
``jax.tree_util.Partial`` over the program's ``predict_tree_ensemble``,
so that the step takes the forest as arguments (put on the device once,
in the ``hybrid.backend_put`` span) rather than as constants. The switch
is fitted from the configuration's ``structure_seed``, so every run
serves the same switch; the pool still comes from the run's seed. The
record holds the forest's ``trees``, ``depth`` and ``nodes`` beside each
call's ``backend_rows``, for ``walk_ns_per_visit`` and
``backend_blocks_per_call``.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from chipbench import cells, grown
from chipbench import forest as fo
from chipbench.gen.janestreet import make_rows
from chipbench.reference import fin_et as ref
from chipbench.systems.fin_xgb import TRACE_KEYS, XgbCell

__all__ = ["CELL", "EtCell", "TRACE_KEYS", "make_models"]

# the one form of the backend the maker grows: scikit-learn's defaults
DEFAULTS = {"kind": "extra_trees", "max_depth": None, "max_features": "sqrt",
            "min_samples_leaf": 1, "bootstrap": False}


def make_models(cfg: dict) -> tuple:
    """(switch, backend), both fixed by the configuration: the switch
    forest on the server's ``switch_features`` from ``structure_seed``,
    the grown backend from ``model_seed``."""
    m = cfg["models"]
    b = m["backend"]
    other = {k: b.get(k) for k in DEFAULTS if b.get(k) != DEFAULTS[k]}
    if other:
        raise ValueError(f"fin_et grows scikit-learn's default extra "
                         f"trees; the configuration asks {other}")
    cols = cfg["server"]["switch_features"]
    x, y = make_rows(m["train_rows"], [m["structure_seed"], 2])
    switch = fo.fit_forest(x[:, cols], y, n_trees=m["switch"]["trees"],
                           depth=m["switch"]["depth"], n_classes=2,
                           structure_seed=m["structure_seed"],
                           seed=[m["structure_seed"], 3])
    xb, yb = make_rows(b["train_rows"], [m["model_seed"], 2])
    backend = grown.grow_forest(
        xb, yb, n_trees=b["trees"], max_features=math.isqrt(xb.shape[1]),
        n_classes=2, seed=[m["model_seed"], 4])
    return switch, backend


class EtCell(XgbCell):
    def __init__(self, spec: dict, seed: int, log):
        import jax
        from jax.tree_util import Partial

        from repro.core.mapping import map_tree_ensemble
        from repro.ml.trees import TreeEnsemble, predict_tree_ensemble
        from repro.serving.hybrid_serving import HybridServer

        if "left" not in {f.name for f in dataclasses.fields(TreeEnsemble)}:
            raise RuntimeError("this program's TreeEnsemble holds no "
                               "node-array trees: fin_et cannot run")
        cfg, traffic = spec["config"], spec["traffic"]
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.calls = cells.source(traffic["kind"], spec["pkg"])
        t0 = time.perf_counter()
        self.pool, _ = make_rows(cfg["pool_rows"], [seed, 1])
        self.switch, self.backend = make_models(cfg)
        be = self.backend
        log(f"[setup] pool rows={len(self.pool)} models "
            f"{time.perf_counter() - t0:.3f}s backend trees="
            f"{be['feat'].shape[0]} nodes={int(be['nodes'].sum())} "
            f"depth={be['depth']}")

        sw = self.switch
        art = jax.device_put(map_tree_ensemble(
            TreeEnsemble(feat=sw["feat"], thresh=sw["thresh"],
                         leaf=sw["leaf"], kind="rf", n_classes=2),
            len(cfg["server"]["switch_features"])), jax.devices()[0])
        forest = TreeEnsemble(feat=be["feat"], thresh=be["thresh"],
                              leaf=be["leaf"], kind="rf", n_classes=2,
                              left=be["left"], depth=be["depth"])
        self.server = HybridServer(
            art, Partial(predict_tree_ensemble, forest), **cfg["server"])
        _, idx = next(self.calls(traffic, len(self.pool), seed))
        np.asarray(self.server.classify(self.pool[idx])[0])

    def results(self, out: dict) -> dict:
        res = super().results(out)
        be = self.backend
        res["record"].update(trees=int(be["feat"].shape[0]),
                             depth=int(be["depth"]),
                             nodes=int(be["nodes"].sum()))
        return res

    def answers(self, rows: list, dtype=np.float32) -> tuple:
        """The reference's (pred, tie) for each call over pool rows
        ``rows``."""
        server = self.cfg["server"]
        ans = ref.pool_answers(self.pool, self.switch, self.backend, server,
                               dtype)
        out = [ref.call_answer(ans, r, server) for r in rows]
        return [p for p, _ in out], [t for _, t in out]


CELL = EtCell
