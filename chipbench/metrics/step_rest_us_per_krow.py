"""Device time of the serving step outside the classify kernel
(dispatch, backend forest, combine), per 1,000 rows. Reads
``step_rest_us_per_krow.<system>``."""

from chipbench.readers import classify_ns, step_ns, us_per_k


def read(rec):
    step = step_ns(rec)
    if not step:
        return None
    return us_per_k(max(step - classify_ns(rec), 0), rec["rows"])
