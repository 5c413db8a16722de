"""Traffic kind ``closed_loop``: one caller.

Each call takes ``batch`` consecutive rows of the pool, wrapping round
it, and is sent as soon as the previous call's predictions are on the
host, so no call waits to be sent and its latency runs from the send.
"""

from __future__ import annotations

import numpy as np


def calls(traffic: dict, n_pool: int, seed: int):
    """-> endless (due offset in seconds, or None for "at once", pool row
    indices) per call."""
    batch = int(traffic["batch"])
    i = 0
    while True:
        yield None, (i * batch + np.arange(batch)) % n_pool
        i += 1
