"""The program's host spans in a reduced trace, and the device idle time
they account for.

``HybridServer.classify`` opens ``hybrid.h2d`` around the conversion of a
call's rows to device arrays and ``hybrid.dispatch`` around the step's
dispatch (``repro.obs.span``, a ``jax.profiler.TraceAnnotation``). They
land on the host thread's line of the same trace as the chip's ops,
where ``DeviceTrace.python`` holds them. A program without these spans
yields no events, and every function here then returns None.

The span means need the host's clock alone. The idle split lays host
spans over device ops and so needs the two clocks in line, which a TPU
v5e trace does not always give: in some recordings every device event
sits 0.3-0.9 ms early against the host's. ``step_order`` tells such a
trace apart.
"""

from __future__ import annotations

import bisect
import re

from chipbench.trace import merged, union_ns

PREFIX = "hybrid."
H2D = "hybrid.h2d"
DISPATCH = "hybrid.dispatch"


def intervals(trace, name: str) -> list:
    """(start_ns, end_ns) of the host spans named ``name``."""
    return [(s, e) for s, e, n in trace.python if n == name]


def mean_us(trace, name: str):
    """Mean duration of the spans named ``name`` in microseconds."""
    got = intervals(trace, name)
    if not got:
        return None
    return sum(e - s for s, e in got) * 1e-3 / len(got)


def idle_split(trace, step: str):
    """Device idle time between the first and the last run of the program
    whose name matches the regex ``step``, split by what the host was in:
    ``{"h2d", "dispatch", "outside", "idle"}`` in ns, plus ``cycles``, the
    number of step-to-step cycles (runs - 1). ``outside`` is idle time in
    which no ``hybrid.*`` span was open. None without spans or with
    fewer than two runs."""
    rx = re.compile(step)
    runs = merged((s, e) for s, e, n in trace.modules if rx.search(n))
    host = [(s, e, n) for s, e, n in trace.python if n.startswith(PREFIX)]
    if len(runs) < 2 or not host:
        return None
    lo, hi = runs[0][0], runs[-1][1]

    def clip(ivs):
        return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]

    busy = clip((s, e) for s, e, *_ in trace.ops)
    idle = (hi - lo) - union_ns(busy)

    def idle_in(ivs):
        return union_ns(clip(ivs) + busy) - union_ns(busy)

    return {"idle": idle,
            "h2d": idle_in(intervals(trace, H2D)),
            "dispatch": idle_in(intervals(trace, DISPATCH)),
            "outside": idle - idle_in([(s, e) for s, e, _ in host]),
            "cycles": len(runs) - 1}


def step_order(trace, step: str):
    """Per call, where its run of the program whose name matches the regex
    ``step`` lies against the host's spans, in ns: ``(lead, tail)``, the
    run's start less the opening of the call's ``hybrid.dispatch`` span,
    and the opening of the next call's ``hybrid.h2d`` span less the run's
    end (None for the last call). The n-th run is paired with the n-th
    dispatch span. Where the clocks line up both are >= 0: the host
    cannot start a step before it dispatches it, and a closed-loop caller
    opens its next call only once the step's predictions are back. None
    when runs and spans do not pair one to one."""
    rx = re.compile(step)
    runs = sorted((s, e) for s, e, n in trace.modules if rx.search(n))
    calls = sorted(s for s, _ in intervals(trace, DISPATCH))
    nxt = sorted(s for s, _ in intervals(trace, H2D))
    if not runs or len(runs) != len(calls):
        return None
    out = []
    for (a, b), d in zip(runs, calls):
        i = bisect.bisect_right(nxt, d)
        out.append((a - d, nxt[i] - b if i < len(nxt) else None))
    return out
