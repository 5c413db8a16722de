"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

A reader gets the run's record: what the cell counted (``rows``,
``calls``), the reduced trace (``trace``), the traced window and busy
time, and ``keys``, the system's names for its programs and kernels in a
TPU trace (program names, opcodes, instruction names, custom-call
targets; see ``trace.py``): ``step``, a regex on the name of the program
that serves one step, and ``classify``, the ``op_ns`` criteria of its
classify kernel."""

from __future__ import annotations


def classify_ns(rec: dict) -> int:
    return rec["trace"].op_ns(**rec["keys"]["classify"])


def step_ns(rec: dict) -> int:
    return rec["trace"].module_ns(rec["keys"]["step"])


def us_per_k(ns: int, n: int):
    """Microseconds per 1,000 items, or None when nothing was measured."""
    if not ns or not n:
        return None
    return ns * 1e-3 / (n / 1000.0)


def idle_pct(rec: dict):
    """Share of the traced window in which no op ran on the chip."""
    if not rec["window_s"]:
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])
