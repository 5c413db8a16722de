"""Device time of the fused classify kernel in the serving step, per
1,000 rows classified. Reads ``fused_classify_us_per_krow.<system>``."""

from chipbench.readers import classify_ns, us_per_k


def read(rec):
    return us_per_k(classify_ns(rec), rec["rows"])
