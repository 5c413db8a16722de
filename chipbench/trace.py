"""From a profiler trace to device times: the benchmark's trace reduction.

Two steps. ``extract`` reads the ``.xplane.pb`` that ``jax.profiler``
writes into plain lists: planes, their lines, and each event as
``[name, start_ns, duration_ns, stats]``. ``DeviceTrace`` reduces such a
dict, so the reduction can be checked on a small recorded trace kept
beside the tests.

What a TPU v5e trace holds (read by hand, JAX 0.9.0): plane
``/device:TPU:<i>`` per chip with a line ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<hash>)``) and a line ``XLA
Ops`` (one event per executed HLO instruction, named by its HLO text,
``%name.N = <shape> <opcode>(<operands>), ...``; the ops of a loop body
appear on their own, inside the interval of the ``while`` op that runs
them). Device and host events share one clock. The ops carry no name-
scope stat: ``jax.named_scope`` reaches them only where XLA names an
instruction after it (the chunk step's classify kernel is
``%fused_classify.N``), so the reduction keys on programs, opcodes,
instruction names and custom-call targets. Host plane ``/host:CPU`` has
a line per thread; the Python threads (``python3``) show what JAX was
asked to do (``PjitFunction(<fn>)``, ``np.asarray(jax.Array)``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def extract(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain lists."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for ev in line.events:
                stats = {}
                for k, v in ev.stats:
                    if isinstance(v, (int, float)) or (
                            isinstance(v, str) and len(v) < 400):
                        stats[k] = v
                evs.append([ev.name, int(ev.start_ns), int(ev.duration_ns),
                            stats])
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    """The union of ``(start, end)`` intervals as disjoint sorted runs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_HLO = re.compile(r"^%([\w.-]+) = .*?([a-z][a-z0-9-]*)\(")


def parse_op(text: str) -> tuple:
    """(instruction name without its .N suffix, opcode, custom-call
    target or '') of an op event's HLO text."""
    m = _HLO.match(text)
    if not m:
        return text, "", ""
    t = re.search(r'custom_call_target="([^"]+)"', text)
    return (re.sub(r"\.\d+$", "", m.group(1)), m.group(2),
            t.group(1) if t else "")


class DeviceTrace:
    """Device times of one chip in an extracted trace."""

    def __init__(self, data: dict, device: int = 0):
        name = f"/device:TPU:{device}"
        planes = [p for p in data["planes"] if p["name"] == name]
        if not planes:
            raise ValueError(f"no plane {name} in the trace; planes: "
                             f"{[p['name'] for p in data['planes']]}")
        lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
        self.ops = [(e[1], e[1] + e[2]) + parse_op(e[0])
                    for e in lines.get(OPS_LINE, [])]
        self.modules = [(e[1], e[1] + e[2], e[0])
                        for e in lines.get(MODULES_LINE, [])]
        self.python = [(ev[1], ev[1] + ev[2], ev[0])
                       for p in data["planes"] if p["name"] == "/host:CPU"
                       for ln in p["lines"] if ln["name"] == "python3"
                       for ev in ln["events"] if ev[2] > 0]

    def busy_ns(self) -> int:
        """Time in which any operation ran on the chip."""
        return union_ns((s, e) for s, e, *_ in self.ops)

    def module_ns(self, pattern: str) -> int:
        """Time of programs whose name matches the regex ``pattern``."""
        return union_ns((s, e) for s, e in self._modules(pattern))

    def _modules(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return merged((s, e) for s, e, n in self.modules if rx.search(n))

    def op_ns(self, *, module: str = "", name: str = "", opcode: str = "",
              target: str = "") -> int:
        """Time of the ops that match every criterion given: the program
        they run in (regex on its name), their instruction name (regex),
        opcode and custom-call target (exact)."""
        rx = re.compile(name) if name else None
        spans = self._modules(module) if module else None
        out = []
        for s, e, n, op, tg in self.ops:
            if (rx and not rx.search(n)) or (opcode and op != opcode) or (
                    target and tg != target):
                continue
            if spans is not None:
                i = bisect.bisect_right(spans, [s, float("inf")]) - 1
                if i < 0 or s >= spans[i][1]:
                    continue
            out.append((s, e))
        return union_ns(out)

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` (instruction, opcode) pairs that took most time, as
        [name, seconds]."""
        tot: dict = {}
        for s, e, n, op, _ in self.ops:
            key = f"{n} ({op})"
            tot[key] = tot.get(key, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns * 1e-9] for n, ns in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest gaps between device ops, each named by the
        Python-level host event that covers most of it, as
        [name, seconds]."""
        runs = merged((s, e) for s, e, *_ in self.ops)
        gaps = [(runs[i][1], runs[i + 1][0]) for i in range(len(runs) - 1)]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        out = []
        for gs, ge in gaps:
            best, cover = "no python event", 0
            for hs, he, n in self.python:
                c = min(he, ge) - max(hs, gs)
                if c > cover:
                    best, cover = n, c
            out.append([best, (ge - gs) * 1e-9])
        return out
