"""accuracy_bits: ``-log2`` of the root mean square of the scaled error
``|C - C_ref| / 2^(ea_i + eb_j)`` over every checked entry of the
window's output, against the double-double reference. Every bit the
program gives up moves it; the largest entry's error is held by the
``scaled_err`` check instead. A non-finite entry reads as no bits."""
import math


def read(run):
    if not math.isfinite(run.rms_scaled_err):
        return 0.0
    if run.rms_scaled_err <= 0:
        return None
    return -math.log2(run.rms_scaled_err)
