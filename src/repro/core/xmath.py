"""Error-free floating point transforms and compensated ("double-word") arithmetic.

Two users inside the framework:

* ``df32`` — a pair of float32 arrays ``(hi, lo)`` with ``hi = RN(hi+lo)``.
  This is the accumulation type the Ozaki scheme uses on TPU, where no
  float64 hardware exists. It carries 2x24 = 48 mantissa bits.
* ``dd64`` — double-double on float64. CPU-only oracle used by tests and
  benchmarks as the high-precision reference (the paper's ``C^DD``).

All transforms are branch-free and jit-safe. ``two_prod`` uses Dekker's
split (no FMA requirement — XLA:CPU does not guarantee fused multiply-add).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class DW(NamedTuple):
    """A double-word value: ``value = hi + lo`` exactly, |lo| <= ulp(hi)/2."""

    hi: jax.Array
    lo: jax.Array

    @property
    def dtype(self):
        return self.hi.dtype

    @property
    def shape(self):
        return self.hi.shape


# ----------------------------------------------------------------------------
# Error-free transforms (dtype generic: f32 or f64)
# ----------------------------------------------------------------------------

def two_sum(a, b):
    """Knuth's TwoSum: s + e == a + b exactly."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def fast_two_sum(a, b):
    """Dekker's FastTwoSum. Requires |a| >= |b| (or a == 0)."""
    s = a + b
    e = b - (s - a)
    return s, e


def _split_const(dtype) -> float:
    # Dekker splitting constant 2^ceil(p/2) + 1 where p = mantissa bits.
    if dtype == jnp.float32:
        return float(2 ** 12 + 1)
    if dtype == jnp.float64:
        return float(2 ** 27 + 1)
    raise ValueError(f"unsupported dtype for Dekker split: {dtype}")


def veltkamp_split(a):
    """Split a into hi + lo with non-overlapping half-width mantissas."""
    c = jnp.asarray(_split_const(a.dtype), a.dtype) * a
    hi = c - (c - a)
    lo = a - hi
    return hi, lo


def two_prod(a, b):
    """Dekker's TwoProd: p + e == a * b exactly (no FMA needed)."""
    p = a * b
    a_hi, a_lo = veltkamp_split(a)
    b_hi, b_lo = veltkamp_split(b)
    e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, e


# ----------------------------------------------------------------------------
# Double-word arithmetic (works for df32 and dd64 alike)
# ----------------------------------------------------------------------------

def dw_zeros(shape, dtype=jnp.float32) -> DW:
    z = jnp.zeros(shape, dtype)
    return DW(z, z)


def dw_from_single(x) -> DW:
    return DW(x, jnp.zeros_like(x))


def dw_normalize(hi, lo) -> DW:
    s, e = fast_two_sum(hi, lo)
    return DW(s, e)


def dw_add(x: DW, y: DW) -> DW:
    """Accurate double-word + double-word (AccurateDWPlusDW, 2 two_sums)."""
    s_hi, e_hi = two_sum(x.hi, y.hi)
    s_lo, e_lo = two_sum(x.lo, y.lo)
    c = e_hi + s_lo
    v_hi, v_lo = fast_two_sum(s_hi, c)
    w = e_lo + v_lo
    return dw_normalize(v_hi, w)


def dw_add_single(x: DW, y) -> DW:
    """Double-word + single word."""
    s_hi, e = two_sum(x.hi, y)
    v = x.lo + e
    return dw_normalize(s_hi, v)


def dw_mul_single(x: DW, y) -> DW:
    """Double-word * single word (DWTimesFP, Dekker-based)."""
    p_hi, p_lo = two_prod(x.hi, y)
    p_lo = p_lo + x.lo * y
    return dw_normalize(p_hi, p_lo)


def dw_mul(x: DW, y: DW) -> DW:
    p_hi, p_lo = two_prod(x.hi, y.hi)
    p_lo = p_lo + (x.hi * y.lo + x.lo * y.hi)
    return dw_normalize(p_hi, p_lo)


def dw_neg(x: DW) -> DW:
    return DW(-x.hi, -x.lo)


def dw_sub(x: DW, y: DW) -> DW:
    return dw_add(x, dw_neg(y))


def dw_to_single(x: DW):
    return x.hi + x.lo


# ----------------------------------------------------------------------------
# Exact power-of-two scaling without 64-bit bitcasts
# ----------------------------------------------------------------------------
#
# ``jnp.frexp``/``jnp.ldexp`` read the exponent field through a
# ``bitcast_convert`` of the value to an integer of its own width. XLA:TPU
# has no 64-bit bitcast, so on float64 they do not compile there. The two
# helpers below give the same bits from comparisons, f32 conversions and
# multiplications by exact powers of two; other dtypes go straight to jnp.

def _pow2_f32(n):
    """Exact ``2.0 ** n`` in f32 for int32 n in [-126, 127], from its
    bits (a 32-bit bitcast, which every backend has)."""
    return jax.lax.bitcast_convert_type((n + 127) << 23, jnp.float32)


def _pow2_f64(e):
    """Exact ``2.0 ** e`` in f64 for int32 ``e`` (0 below 2^-1074, inf
    above 2^1023).

    ``e = 4q + r`` with q truncated toward zero: ``2^q`` is a product of
    three f32 powers, squared twice, then times ``2^r``. Every partial
    product is a power of two between 1 and the result, so each multiply
    is exact."""
    e = jnp.clip(e, -1075, 1024)
    q = jnp.where(e < 0, -((-e) >> 2), e >> 2)
    r = e - 4 * q                                   # |r| <= 3
    q1 = jnp.clip(q, -90, 90)                       # |q| <= 269
    q2 = jnp.clip(q - q1, -90, 90)
    p = (_pow2_f32(q1).astype(jnp.float64) *
         _pow2_f32(q2).astype(jnp.float64) *
         _pow2_f32(q - q1 - q2).astype(jnp.float64))
    p = p * p
    p = p * p
    return p * _pow2_f32(r).astype(jnp.float64)


@jax.jit
def exponent(x):
    """``jnp.frexp(x)[1]`` bitwise (int32): ``2^(e-1) <= |x| < 2^e`` for
    finite nonzero x, 0 for zeros, infinities and NaNs.

    f64: four exact power-of-two rescalings bring ``|x|`` into the f32
    normal range, the f32 exponent is read there, and one exact f64
    comparison undoes the case where the f32 rounding crossed a power of
    two."""
    x = jnp.asarray(x)
    if x.dtype != jnp.float64:
        return jnp.frexp(x)[1]
    ax = jnp.abs(x)
    shift = jnp.zeros(x.shape, jnp.int32)
    for t in (512, 256, 128, 64):       # ends in [2^-114, 2^64)
        big = ax >= 2.0 ** t
        small = ax < 2.0 ** -t
        ax = jnp.where(big, ax * 2.0 ** -t,
                       jnp.where(small, ax * 2.0 ** t, ax))
        shift = shift + jnp.where(big, t, jnp.where(small, -t, 0))
    _, e32 = jnp.frexp(ax.astype(jnp.float32))
    floor = _pow2_f32(e32 - 1).astype(jnp.float64)
    e = e32 - (ax < floor).astype(jnp.int32) + shift
    special = jnp.isinf(x) | jnp.isnan(x) | (x == 0)
    return jnp.where(special, 0, e).astype(jnp.int32)


@jax.jit
def ldexp(x, n):
    """``jnp.ldexp(x, n)`` bitwise wherever the result is a normal
    number: ``x * 2^n`` with one rounding.

    f64: two multiplies by exact powers of two, the first by
    ``2^clip(n, -1022, 1023)``. For n in that range the second factor is
    1; above it both scale up (exact until a true overflow); below it
    the first step is exact for |x| >= 1. Only results under 2^-1022 can
    round twice — the subnormal range XLA:CPU flushes anyway (subnormal
    inputs, which CPU arithmetic flushes too, are outside the contract)."""
    x = jnp.asarray(x)
    if x.dtype != jnp.float64:
        return jnp.ldexp(x, n)
    n = jnp.asarray(n, jnp.int32)
    first = jnp.clip(n, -1022, 1023)
    out = x * _pow2_f64(first) * _pow2_f64(n - first)
    return jnp.where(jnp.isinf(x) | (x == 0), x, out)   # no 0 * inf


def native_f64() -> bool:
    """Whether float64 routes run on the default backend: every backend
    but XLA:TPU, which has no float64 unit and emulates it."""
    return jax.default_backend() != "tpu"


def refuse_f64_on_tpu(what: str) -> None:
    """Refuse a float64 route on a TPU backend.

    XLA:TPU rewrites float64 into 32-bit operations; the error-free split
    and the f64 accumulation assume IEEE binary64, which nothing shows
    that emulation to be (``chip_smoke.py``'s device phase probes it).
    The route that runs on a TPU is the double-float32 one, and it is
    never taken silently.
    """
    if not native_f64():
        raise TypeError(
            f"{what}: float64 operands are refused on TPU (XLA:TPU emulates "
            f"float64; the f64 routes assume IEEE binary64). Use the DW "
            f"(df32) route: repro.matmul(DW(a_hi, a_lo), DW(b_hi, b_lo), "
            f"precision='ozaki-fp64/pallas_fused+epilogue'), with "
            f"core.xmath.df32_from_f64 to split float64 data")


# ----------------------------------------------------------------------------
# df32 <-> f64 conversion (CPU-side bridging; f64 requires x64 mode)
# ----------------------------------------------------------------------------

def df32_from_f64(x) -> DW:
    """Exactly decompose float64 into (f32 hi, f32 lo) pairs.

    Exact whenever x's mantissa fits 48 bits and its exponent is in f32
    range; otherwise lo absorbs the nearest representable remainder.
    """
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(x.dtype)).astype(jnp.float32)
    return DW(hi, lo)


def df32_to_f64(x: DW):
    return x.hi.astype(jnp.float64) + x.lo.astype(jnp.float64)


# ----------------------------------------------------------------------------
# dd64 oracle matmul (the paper's double-double reference C^DD)
# ----------------------------------------------------------------------------

def dd_matmul_f64(a: jax.Array, b: jax.Array) -> DW:
    """Double-double accurate C = A @ B on float64 inputs (CPU oracle).

    Sequential compensated accumulation over k; vectorized over (m, n).
    Cost ~20x a plain f64 matmul of the same shape — use moderate sizes.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2

    def body(carry, idx):
        c_hi, c_lo = carry
        # outer product column step: a[:, idx] (m,) x b[idx, :] (n,)
        p, pe = two_prod(a[:, idx][:, None], b[idx, :][None, :])
        s, e = two_sum(c_hi, p)
        c_lo = c_lo + (e + pe)
        c_hi, c_lo = fast_two_sum(s, c_lo)
        return (c_hi, c_lo), None

    init = (jnp.zeros((m, n), a.dtype), jnp.zeros((m, n), a.dtype))
    (c_hi, c_lo), _ = jax.lax.scan(body, init, jnp.arange(k))
    return DW(c_hi, c_lo)


def dd_matmul_np(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """NumPy fallback double-double matmul (no jax tracing, pure f64)."""
    m, k = a.shape
    _, n = b.shape
    c_hi = np.zeros((m, n))
    c_lo = np.zeros((m, n))
    split = 2.0 ** 27 + 1

    for t in range(k):
        x = a[:, t][:, None]
        y = b[t, :][None, :]
        p = x * y
        cx = split * x
        x_hi = cx - (cx - x)
        x_lo = x - x_hi
        cy = split * y
        y_hi = cy - (cy - y)
        y_lo = y - y_hi
        pe = ((x_hi * y_hi - p) + x_hi * y_lo + x_lo * y_hi) + x_lo * y_lo
        s = c_hi + p
        bb = s - c_hi
        e = (c_hi - (s - bb)) + (p - bb)
        c_lo = c_lo + (e + pe)
        c_hi = s + c_lo
        c_lo = c_lo - (c_hi - s)
    return c_hi, c_lo


def rel_error_vs_dd(c: np.ndarray, dd_hi: np.ndarray, dd_lo: np.ndarray) -> np.ndarray:
    """Paper Eq. (7): |C - C_dd| / |C_dd| elementwise (safe at 0)."""
    ref = dd_hi + dd_lo
    denom = np.where(ref == 0.0, 1.0, np.abs(ref))
    num = np.abs((c - dd_hi) - dd_lo)
    return num / denom
