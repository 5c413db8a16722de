"""Mean duration of the program's ``hybrid.dispatch`` host span: the
call into the serving step until it returns to the caller (the step
runs on after it). Reads ``classify_dispatch_us_per_call.<system>``."""

from chipbench.spans import DISPATCH, mean_us


def read(rec):
    return mean_us(rec["trace"], DISPATCH)
