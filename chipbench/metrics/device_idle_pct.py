"""Share of the traced window in which no op ran on chip 0, from the
union of the device's op intervals. Reads ``device_idle_pct.<cell>`` for
any cell."""

from chipbench.readers import idle_pct


def read(rec):
    return idle_pct(rec)
