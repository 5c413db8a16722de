"""Runs the finance deployment with its XGBoost backend:
``HybridServer.classify`` on whole 130-feature rows.

As ``fin_batch`` runs its cells, with three differences. The pool keeps
all 130 columns, and the server's ``switch_features`` (from the
configuration's ``server`` group, passed whole) name the 5 the switch
parses, so the step slices them for the switch and hands the backend the
whole forwarded rows. The backend is a boosted forest (``boost.py``)
made from the configuration's ``model_seed``, its weights constants of
the step, and its margin is walked by the program's
``predict_tree_ensemble``. Each call's ``HybridStats.backend_rows``, the
dispatch layer's counter, is kept as a device array in the window and
read after it, for ``backend_fill_pct``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import boost, cells
from chipbench import forest as fo
from chipbench.gen.janestreet import make_rows
from chipbench.reference import fin_xgb as ref
from chipbench.systems.fin_batch import TRACE_KEYS, FinCell

__all__ = ["CELL", "TRACE_KEYS", "XgbCell", "make_models"]


def make_models(cfg: dict, seed) -> tuple:
    """(switch, backend): the switch forest on the server's
    ``switch_features`` from the run's seed, as in ``fin_batch``, and the
    boosted backend from the configuration's
    ``model_seed``: its weights are compiled into the step, so a backend
    drawn from the run's seed would recompile the step in every run."""
    m = cfg["models"]
    b = m["backend"]
    if b["kind"] != "xgb":
        raise ValueError(f"fin_xgb serves an xgb backend, not {b['kind']!r}")
    x, y = make_rows(m["train_rows"], [seed, 2])
    switch = fo.fit_forest(x[:, cfg["server"]["switch_features"]], y,
                           n_trees=m["switch"]["trees"],
                           depth=m["switch"]["depth"], n_classes=2,
                           structure_seed=m["structure_seed"],
                           seed=[seed, 3])
    xb, _ = make_rows(m["train_rows"], [m["model_seed"], 2])
    backend = boost.make_boosted(
        xb, n_trees=b["trees"], depth=b["depth"],
        learning_rate=b["learning_rate"], subsample=b["subsample"],
        colsample_bytree=b["colsample_bytree"], base_score=b["base_score"],
        seed=[m["model_seed"], 4])
    return switch, backend


class XgbCell(FinCell):
    def __init__(self, spec: dict, seed: int, log):
        import jax
        from repro.core.mapping import map_tree_ensemble
        from repro.ml.trees import TreeEnsemble, predict_tree_ensemble
        from repro.serving.hybrid_serving import HybridServer

        cfg, traffic = spec["config"], spec["traffic"]
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.calls = cells.source(traffic["kind"], spec["pkg"])
        t0 = time.perf_counter()
        self.pool, _ = make_rows(cfg["pool_rows"], [seed, 1])
        self.switch, self.backend = make_models(cfg, seed)
        log(f"[setup] pool rows={len(self.pool)} models "
            f"{time.perf_counter() - t0:.3f}s")

        sw, be = self.switch, self.backend
        dev = jax.devices()[0]
        art = jax.device_put(map_tree_ensemble(
            TreeEnsemble(feat=sw["feat"], thresh=sw["thresh"],
                         leaf=sw["leaf"], kind="rf", n_classes=2),
            len(cfg["server"]["switch_features"])), dev)
        big = jax.device_put(TreeEnsemble(
            feat=be["feat"], thresh=be["thresh"], leaf=be["leaf"][..., None],
            kind="xgb", base_score=be["base_score"],
            learning_rate=be["learning_rate"], n_classes=2), dev)
        self.server = HybridServer(
            art, lambda r: predict_tree_ensemble(big, r), **cfg["server"])
        _, idx = next(self.calls(traffic, len(self.pool), seed))
        np.asarray(self.server.classify(self.pool[idx])[0])

    def serve(self, seconds: float) -> dict:
        """``FinCell.serve``, keeping each call's ``backend_rows`` as the
        device array ``classify`` returns: no sync is added."""
        sent = []
        classify = self.server.classify

        def recording(x):
            pred, stats = classify(x)
            sent.append(stats.as_arrays()[1])
            return pred, stats

        self.server.classify = recording
        try:
            out = super().serve(seconds)
        finally:
            del self.server.classify
        return dict(out, backend_rows=sent)

    def results(self, out: dict) -> dict:
        res = super().results(out)
        res["record"].update(
            backend_rows=[int(r) for r in out["backend_rows"]],
            capacity=self.cfg["server"]["capacity"])
        return res

    def answers(self, rows: list, dtype=np.float32) -> tuple:
        """The reference's (pred, tie) for each call over pool rows
        ``rows``."""
        server = self.cfg["server"]
        ans = ref.pool_answers(self.pool, self.switch, self.backend,
                               server["switch_features"], dtype)
        out = [ref.call_answer(ans, r, server) for r in rows]
        return [p for p, _ in out], [t for _, t in out]


CELL = XgbCell
