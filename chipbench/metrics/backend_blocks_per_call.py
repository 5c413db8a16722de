"""Mean number of backend row blocks a call ran: ``ceil(backend_rows /
B)`` per call, over the calls of the window, from each call's
``HybridStats.backend_rows`` (the dispatch layer's counter, recorded per
call). ``B`` is 128 where the capacity is a multiple of it, else the
capacity (``HybridStats.backend_blocks``' rule). Reads
``backend_blocks_per_call.<system>``; silent for a system that records no
``backend_rows``."""


def blocks(rec):
    """-> (blocks the backend ran, per call; ``B``), or None without a
    record."""
    sent, cap = rec.get("backend_rows"), rec.get("capacity")
    if not sent or not cap:
        return None
    b = 128 if cap % 128 == 0 else cap
    return [-(-r // b) for r in sent], b


def read(rec):
    got = blocks(rec)
    if got is None:
        return None
    per_call, _ = got
    return sum(per_call) / len(per_call)
