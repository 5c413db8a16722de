"""Mean duration of the program's ``hybrid.h2d`` host span: the
conversion of a call's rows and threshold to device arrays, on the
trace's clock. Reads ``classify_h2d_us_per_call.<system>``."""

from chipbench.spans import H2D, mean_us


def read(rec):
    return mean_us(rec["trace"], H2D)
