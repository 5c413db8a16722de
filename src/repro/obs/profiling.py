"""Spans on the profiler's clock, per-stage host timing, and the scopes of
a compiled step.

* ``span(name, **ids)`` — the one span primitive of the program: a
  ``jax.profiler.TraceAnnotation`` named ``name`` whose keyword ids
  (``call=7``) become stats of the event. Outside a profiler capture it
  costs a TraceMe no-op; inside one it lands on the host thread's line
  of the same trace as the device's ops, so a device idle gap can be
  laid against what the host was doing. The two clocks do not always
  agree: on a TPU v5e a process can record every device event about a
  millisecond early against the host's, so check the order of a step
  and the span that dispatched it before such a split.

* ``StageTimer.stage(name)`` — wall-time a pipeline stage (ring cut,
  host pack, H2D transfer, megastep dispatch, backend flush,
  back-patch). Durations accumulate per stage with a bounded sample
  ring for percentiles; thread-safe enough for the prefetch thread
  (list/deque appends are atomic under the GIL). JAX dispatch is
  asynchronous, so a stage around a dispatch times the enqueue; device
  time comes from a profiler trace. ``Observability.stage`` opens a
  ``span`` of the same name around the timing.

* ``op_scopes(hlo_text)`` — which ``jax.named_scope`` each instruction of
  an optimized HLO module ran under. TPU op events in a trace carry the
  instruction's name (``%fusion.9 = ...``) but no scope, so this map is
  how a trace's device time is split by the step's scopes.

Stage vocabulary used by the serving tiers (DESIGN.md §14): ``ring_cut``
(pull source + admit + window-granular pack), ``h2d`` (HostCut ->
device PacketChunk transfer; queue wait when the prefetch thread owns
the transfer), ``megastep`` (step dispatch), ``backend_flush`` (host
backend call on the two-phase path), ``backpatch`` (jitted back-patch
dispatch). The register scan and fused classify live *inside* the
megastep's single dispatch — they are separated with ``jax.named_scope``
metadata in the jitted graphs (zero runtime cost) and show up in
profiler traces, not host timers.
"""

from __future__ import annotations

import collections
import contextlib
import re
import time
from typing import Callable

import jax
import numpy as np

STAGES = ("ring_cut", "h2d", "megastep", "backend_flush", "backpatch")


def span(name: str, **ids):
    """A host span ``name`` on the profiler's clock, with ``ids`` (ints or
    short strings, e.g. ``call=7``) as stats of the event; see the
    module doc."""
    return jax.profiler.TraceAnnotation(name, **ids)


class StageTimer:
    """Accumulate wall durations per named stage (bounded memory)."""

    def __init__(self, *, clock: Callable[[], float] = time.perf_counter,
                 max_samples: int = 4096):
        self._clock = clock
        self._max = max_samples
        self._acc: dict = {}     # name -> [n, total_s, max_s, deque]

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = self._clock()
        try:
            yield
        finally:
            self.record(name, self._clock() - t0)

    def record(self, name: str, seconds: float) -> None:
        acc = self._acc.get(name)
        if acc is None:
            acc = self._acc[name] = [0, 0.0, 0.0,
                                     collections.deque(maxlen=self._max)]
        acc[0] += 1
        acc[1] += seconds
        acc[2] = max(acc[2], seconds)
        acc[3].append(seconds)

    @property
    def stages(self) -> tuple:
        return tuple(self._acc)

    def count(self, name: str) -> int:
        acc = self._acc.get(name)
        return acc[0] if acc else 0

    def total(self, name: str) -> float:
        acc = self._acc.get(name)
        return acc[1] if acc else 0.0

    def summary(self) -> dict:
        """stage -> {n, total_s, mean_ms, p50_ms, p95_ms, max_ms}."""
        out = {}
        for name, (n, total, mx, samples) in sorted(self._acc.items()):
            s = np.fromiter(samples, np.float64) * 1e3
            p50, p95 = (np.percentile(s, (50, 95)) if s.size
                        else (float("nan"), float("nan")))
            out[name] = {"n": n, "total_s": total,
                         "mean_ms": total / n * 1e3 if n else None,
                         "p50_ms": float(p50) if s.size else None,
                         "p95_ms": float(p95) if s.size else None,
                         "max_ms": mx * 1e3}
        return out

    def reset(self) -> None:
        self._acc.clear()




_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply)=%([\w.\-]+)")


def _scope(op_name: str):
    """The scope of ``jit(<fn>)/<scope>/.../<op>``: the element after the
    outer ``jit(...)``, when another element follows it (a bare
    ``jit(<fn>)/<op>`` ran under no scope)."""
    parts = op_name.split("/")
    return parts[1] if len(parts) > 2 else None


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: scope} of an optimized HLO module's text
    (``jax.jit(f).lower(...).compile().as_text()``).

    The name keeps its ``.N`` (``fusion.9``), as a TPU op event's text
    starts (``%fusion.9 = ...``). The scope is the first element of the
    instruction's ``op_name`` after the outer ``jit(<fn>)/``. An
    instruction whose own metadata names no scope, such as a fusion XLA
    made, takes the scope that most instructions of the computations it
    calls (``calls=``, ``to_apply=``) have, found the same way.
    Instructions with neither are left out."""
    own, callees, comps = {}, {}, collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line.endswith("{") and not line.startswith(" "):
                head = line.split()
                comp = head[1 if head[0] == "ENTRY" else 0].lstrip("%")
            continue
        name = m.group(1)
        comps[comp].append(name)
        op = _OP_NAME.search(line)
        scope = _scope(op.group(1)) if op else None
        if scope is not None:
            own[name] = scope
        else:
            callees[name] = _CALLED.findall(line)

    memo: dict = {}

    def resolve(name):
        if name in own:
            return own[name]
        if name not in memo:
            memo[name] = None              # HLO calls form no cycle
            votes = collections.Counter(
                s for c in callees.get(name, ()) for i in comps.get(c, ())
                if (s := resolve(i)) is not None)
            memo[name] = votes.most_common(1)[0][0] if votes else None
        return memo[name]

    return {n: s for names in comps.values() for n in names
            if (s := resolve(n)) is not None}
