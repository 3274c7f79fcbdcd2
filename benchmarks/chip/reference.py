"""The plain reference: a double-double matrix product on the host CPU.

Copied in spirit from the paper's ``C^DD`` oracle and written here anew
in NumPy, so that nothing the program under test computes, imports or
tables reaches it. Each product term is made exact with Dekker's split
(no fused multiply-add needed) and summed with Knuth's two-sum into a
(hi, lo) pair; the loop runs over k and is vectorised over every checked
block at once, so many calls' blocks cost one pass.

``scaled_error`` is the measure the Ozaki scheme's error bounds are
stated in: ``max |C - C_ref| / 2^(ea_i + eb_j)``, with ``2^ea_i`` the
power of two strictly above row i's largest magnitude (and ``eb_j`` the
same for column j of B).
"""
from __future__ import annotations

import numpy as np

_SPLIT = 2.0 ** 27 + 1          # Dekker's constant for binary64


def _halves(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    c = _SPLIT * x
    hi = c - (c - x)
    return hi, x - hi


def dd_matmul(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``a @ b`` in double-double: a ``(..., r, k)``, b ``(..., k, c)``
    float64, batched over the leading axes. Returns ``(hi, lo)``."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    k = a.shape[-1]
    if b.shape[-2] != k:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    a_hi, a_lo = _halves(a)
    b_hi, b_lo = _halves(b)
    shape = np.broadcast_shapes(a.shape[:-1] + (1,), b.shape[:-2] + (1,)
                                + b.shape[-1:])
    hi = np.zeros(shape)
    lo = np.zeros(shape)
    for t in range(k):
        x, xh, xl = a[..., t, None], a_hi[..., t, None], a_lo[..., t, None]
        y, yh, yl = (b[..., t, None, :], b_hi[..., t, None, :],
                     b_lo[..., t, None, :])
        p = x * y
        pe = ((xh * yh - p) + xh * yl + xl * yh) + xl * yl   # p + pe == x*y
        s = hi + p
        bb = s - hi
        e = (hi - (s - bb)) + (p - bb)                       # s + e == hi + p
        lo = lo + (e + pe)
        hi = s + lo
        lo = lo - (hi - s)
    return hi, lo


def row_exponents(x: np.ndarray, axis: int = -1) -> np.ndarray:
    """e with ``2^(e-1) <= max |x| < 2^e`` along ``axis`` (0 for a zero
    row)."""
    amax = np.max(np.abs(x), axis=axis)
    _, e = np.frexp(amax)
    return np.where(amax > 0, e, 0)


def scaled_errors(c: np.ndarray, ref_hi: np.ndarray, ref_lo: np.ndarray,
                  a_rows: np.ndarray, b_cols: np.ndarray) -> np.ndarray:
    """``|c - ref| / 2^(ea_i + eb_j)`` entry by entry, batched over
    leading axes: c ``(..., r, c)``, a_rows ``(..., r, k)``, b_cols
    ``(..., k, c)``. A non-finite entry of ``c`` reads as infinite."""
    ea = row_exponents(a_rows, axis=-1)[..., :, None]
    eb = row_exponents(b_cols, axis=-2)[..., None, :]
    diff = np.abs((np.asarray(c, np.float64) - ref_hi) - ref_lo)
    diff = np.where(np.isfinite(diff), diff, np.inf)
    return np.ldexp(diff, -(ea + eb))


def scaled_error(c: np.ndarray, ref_hi: np.ndarray, ref_lo: np.ndarray,
                 a_rows: np.ndarray, b_cols: np.ndarray) -> float:
    """``max |c - ref| / 2^(ea_i + eb_j)`` over one block (see
    ``scaled_errors``)."""
    return float(np.max(scaled_errors(c, ref_hi, ref_lo, a_rows, b_cols)))
