"""Runs the per-request cells: ``HybridServer.classify``.

Set-up makes the row pool and the training samples from the seed, fits
the forests, maps the switch forest with the program's mapping tool and
builds the server from the configuration's ``server`` group, passed
whole (a key the server does not take is an error); one call of the
cell's own batch compiles the step. The traffic's ``kind`` names the
source (``sources/<kind>.py``) that says when each call is due and which
pool rows it carries. A call's latency runs from the time it was due (or
sent, for a call due at once) to its predictions on the host.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import cells
from chipbench import forest as fo
from chipbench.gen.janestreet import SWITCH_FEATURES, make_rows
from chipbench.reference import fin_batch as ref

# names of the step and its classify kernel in a TPU trace: the step is
# ``jit_step``, and its only Pallas call is the fused classify kernel
TRACE_KEYS = {"step": r"^jit_step\(",
              "classify": {"module": r"^jit_step\(",
                           "target": "tpu_custom_call"}}


def make_models(cfg: dict, seed) -> tuple:
    """(switch, backend) forests: the switch from the run's seed, the
    backend from the configuration's ``model_seed``. The backend's weights
    are compiled into the step as constants, so a backend drawn from the
    run's seed would recompile the step in every run."""
    m = cfg["models"]
    x, y = make_rows(m["train_rows"], [seed, 2])
    switch = fo.fit_forest(x[:, SWITCH_FEATURES], y,
                           n_trees=m["switch"]["trees"],
                           depth=m["switch"]["depth"], n_classes=2,
                           structure_seed=m["structure_seed"],
                           seed=[seed, 3])
    xb, yb = make_rows(m["train_rows"], [m["model_seed"], 2])
    backend = fo.fit_forest(xb[:, SWITCH_FEATURES], yb,
                            n_trees=m["backend"]["trees"],
                            depth=m["backend"]["depth"], n_classes=2,
                            structure_seed=m["structure_seed"] + 1,
                            seed=[m["model_seed"], 4])
    return switch, backend


class FinCell:
    def __init__(self, spec: dict, seed: int, log):
        import jax
        from repro.core.mapping import map_tree_ensemble
        from repro.ml.trees import TreeEnsemble, predict_tree_ensemble
        from repro.serving.hybrid_serving import HybridServer

        cfg, traffic = spec["config"], spec["traffic"]
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.calls = cells.source(traffic["kind"], spec["pkg"])
        t0 = time.perf_counter()
        x, _ = make_rows(cfg["pool_rows"], [seed, 1])
        self.pool = np.ascontiguousarray(x[:, SWITCH_FEATURES])
        self.switch, self.backend = make_models(cfg, seed)
        log(f"[setup] pool rows={len(self.pool)} models "
            f"{time.perf_counter() - t0:.3f}s")

        def ens(f):
            return TreeEnsemble(feat=f["feat"], thresh=f["thresh"],
                                leaf=f["leaf"], kind="rf", n_classes=2)

        dev = jax.devices()[0]
        art = jax.device_put(
            map_tree_ensemble(ens(self.switch), len(SWITCH_FEATURES)), dev)
        big = jax.device_put(ens(self.backend), dev)
        self.server = HybridServer(
            art, lambda r: predict_tree_ensemble(big, r), **cfg["server"])
        _, idx = next(self.calls(traffic, len(self.pool), seed))
        np.asarray(self.server.classify(self.pool[idx])[0])

    def serve(self, seconds: float) -> dict:
        lat, preds, rows = [], [], []
        t_start = time.monotonic()
        t_end = t_start + seconds
        for due, idx in self.calls(self.traffic, len(self.pool), self.seed):
            if time.monotonic() >= t_end:
                break
            x = self.pool[idx]
            now = time.monotonic()
            if due is not None:
                t0 = t_start + due
                if t0 > now:
                    time.sleep(t0 - now)
            else:
                t0 = now
            pred = np.asarray(self.server.classify(x)[0])
            lat.append(time.monotonic() - t0)
            preds.append(pred)
            rows.append(idx)
        return dict(preds=preds, rows=rows, latency=np.asarray(lat),
                    calls=len(lat))

    def results(self, out: dict) -> dict:
        lat = out["latency"]
        return dict(attempted=out["calls"], failed=0,
                    e2e={"fin_p99_ms": float(np.percentile(lat, 99)) * 1e3},
                    record=dict(calls=out["calls"],
                                rows=sum(len(r) for r in out["rows"]),
                                keys=TRACE_KEYS))

    def answers(self, rows: list, dtype=np.float32) -> tuple:
        """The reference's (pred, tie) for each call over pool rows
        ``rows``. Rows are independent, so each pool row is answered
        once."""
        ans = ref.pool_answers(self.pool, self.switch, self.backend, dtype)
        out = [ref.call_answer(ans, r, self.cfg["server"]) for r in rows]
        return [p for p, _ in out], [t for _, t in out]

    def check(self, out: dict) -> dict:
        want, tie = self.answers(out["rows"])
        return compare(out["preds"], want, tie)

    def control(self, out: dict, dtype) -> dict:
        """The reference in ``dtype`` put in the program's place."""
        got, _ = self.answers(out["rows"], dtype)
        want, tie = self.answers(out["rows"])
        return compare(got, want, tie)


def compare(preds: list, want: list, ties: list) -> dict:
    """Predictions that differ from the reference's, where the reference's
    two best backend classes are not within rounding of each other; a
    call with a missing or misshapen answer counts all its rows."""
    bad = 0
    for p, w, t in zip(preds, want, ties):
        bad += int(((p != w) & ~t).sum()) if p.shape == w.shape else len(w)
    return dict(pred_mismatch=bad)


CELL = FinCell
