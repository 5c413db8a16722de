"""Share of the backend buffer that the dispatch filled: the rows sent to
the backend over all calls of the window (``HybridStats.backend_rows``,
recorded per call), over calls times ``capacity``. The backend evaluates
the whole buffer on every call, so the rest is work on padding. Reads
``backend_fill_pct.<system>``; silent for a system that records no
``backend_rows``."""


def read(rec):
    sent = rec.get("backend_rows")
    if not sent or not rec.get("capacity"):
        return None
    return 100.0 * sum(sent) / (len(sent) * rec["capacity"])
