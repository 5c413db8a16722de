"""Device time of the serving step outside the classify kernel, in
nanoseconds per node visit of the backend's walk: visits are, over the
calls of the window, the backend's row blocks (``ceil(backend_rows /
B)`` per call, ``backend_blocks_per_call``) times ``B`` rows times the
forest's trees times its depth, the levels each row's walk takes. Reads
``walk_ns_per_visit.<system>``; silent for a system that records no
forest (``trees``, ``depth``) or no ``backend_rows``, and where no block
ran."""

from chipbench.metrics.backend_blocks_per_call import blocks
from chipbench.readers import classify_ns, step_ns


def read(rec):
    if not rec.get("trees") or not rec.get("depth"):
        return None
    got = blocks(rec)
    step = step_ns(rec)
    if got is None or not step:
        return None
    per_call, b = got
    visits = sum(per_call) * b * rec["trees"] * rec["depth"]
    if not visits:
        return None
    return max(step - classify_ns(rec), 0) / visits
