"""SplitInt — Algorithm 4 of the paper, adapted for exact signed extraction.

Splits an ``m x k`` matrix row-wise into ``s`` int8 mantissa slices relative
to a shared per-row power-of-two exponent (block-float). The extraction is
*error-free*: with ``w`` bits per slice,

    M[i, j]  ==  2**exp[i] * sum_p slice[p, i, j] * 2**(-(p+1) * w)  +  tail

where ``tail`` is the (truncated) residual below the kept mantissa space.
Every slice value lies in ``[-2**w, 2**w - 1] ⊆ [-128, 127]``.

Implementation notes (documented in DESIGN.md):

* Extraction is sign-magnitude, exactly as the paper presents Alg. 4:
  the residual is kept nonnegative so ``t - floor(t)`` is exact in
  floating point (for a *negative* residual that subtraction needs one
  extra mantissa bit and silently rounds — a bug this module originally
  had, caught by the exact-reconstruction property test).
* The shared exponent is strictly greater than the row max
  (``2**(floor(log2 max) + 1)``), so the scaled residual is in [0, 1)
  and a slice magnitude never exceeds 2**w - 1 <= 127.
* ``alpha`` uses an exact integer overflow check ``k_terms * 2**(wa+wb)
  <= 2**31 - 1`` instead of Eq. (4)'s floor, which admits a one-off
  overflow corner at exact powers of two.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import tracing
from .xmath import DW, exponent, fast_two_sum, ldexp, two_sum

INT8_MIN, INT8_MAX = -128, 127

# Shared exponent assigned to all-zero rows. Any finite value yields the
# correct all-zero slices (ldexp(0, -e) == 0); what matters is that the
# sentinel IS finite: a log2-style exponent of an empty row is -inf, and
# -inf reaching the 2**exp scales turns the whole pipeline into NaNs on
# the pinned jax (whose exp2 is additionally inexact at extreme
# arguments — ldexp with finite int32 exponents sidesteps both hazards).
# Zero-cancellation workloads (paper Fig. 7) and padded/sparse serving
# batches hit this case routinely.
ZERO_ROW_EXP = 0


class SplitResult(NamedTuple):
    """Result of SplitInt for one matrix (row-wise sharing).

    slices: (s, m, k) int8 mantissa slices, most significant first.
    exp:    (m,) int32 shared per-row exponents (value scale = 2**exp).
    w:      python int, bits kept per slice (BPS).
    """

    slices: jax.Array
    exp: jax.Array
    w: int


def compute_alpha(k: int, *, ell_acc: int = 31, fuse_terms: int = 1) -> int:
    """Max slice bit width avoiding accumulator overflow — Eq. (3)/(4).

    ``fuse_terms`` > 1 reserves headroom for summing that many slice-GEMM
    products exactly in the integer accumulator (diagonal fusion, O1).
    """
    if k <= 0:
        raise ValueError("k must be positive")
    limit = 2 ** ell_acc - 1
    alpha = (ell_acc - max(0, (k * fuse_terms - 1)).bit_length()) // 2
    # exact check (covers the power-of-two equality corner)
    while alpha > 0 and k * fuse_terms * 4 ** alpha > limit:
        alpha -= 1
    while k * fuse_terms * 4 ** (alpha + 1) <= limit:
        alpha += 1
    return alpha


def slice_width(k: int, *, ell_acc: int = 31, ell_in: int = 7,
                fuse_terms: int = 1) -> int:
    """BPS = min(alpha, ell_in) — Eq. (5)."""
    return max(1, min(compute_alpha(k, ell_acc=ell_acc, fuse_terms=fuse_terms),
                      ell_in))


@tracing.scoped(tracing.EXPONENTS)
def row_exponents(m: jax.Array) -> jax.Array:
    """Strict power-of-two row exponents: 2**exp > max_j |M_ij| (int32).

    All-zero rows are clamped to the finite ``ZERO_ROW_EXP`` sentinel —
    never a ``-inf``-style "empty max" exponent, which would propagate
    NaN/overflow through the power-of-two scales downstream (the split's
    ``ldexp``, the deferred ``e_base`` application, and the exponent
    statistics in ``core.accuracy``).
    """
    amax = jnp.max(jnp.abs(m), axis=-1)
    # frexp: x = mant * 2**e with mant in [0.5, 1)  ->  2**e >= |x|, strict
    # unless mant == 0.5 exactly (x a power of two), where 2**e == 2*x > x.
    e = exponent(amax)
    return jnp.where(amax > 0, e, ZERO_ROW_EXP).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_splits", "w"))
def split_int(m: jax.Array, num_splits: int, w: int,
              exp: jax.Array | None = None) -> SplitResult:
    """SplitInt on a float matrix (f64 on CPU, f32 acceptable for tests).

    Rows share the exponent; to split ``B`` column-wise pass ``B.T``.
    ``exp``: precomputed per-row exponents — the distributed path passes
    the global (all-reduced max) exponents so every k-shard splits against
    the same mantissa space.
    """
    if exp is None:
        exp = row_exponents(m)
    sign = jnp.where(m < 0, -1, 1).astype(jnp.int8)
    r = ldexp(jnp.abs(m), -exp[:, None]).astype(m.dtype)  # exact, [0, 1)
    scale = jnp.asarray(2.0 ** w, m.dtype)

    def body(r, _):
        t = r * scale                      # exact (power-of-two scale)
        y = jnp.floor(t)                   # in [0, 2**w - 1]
        r = t - y                          # exact: nonneg fraction suffix
        return r, (sign * y.astype(jnp.int8))

    _, slices = jax.lax.scan(body, r, None, length=num_splits)
    return SplitResult(slices, exp, w)


@functools.partial(jax.jit, static_argnames=("num_splits", "w"))
def split_int_dw(m: DW, num_splits: int, w: int) -> SplitResult:
    """SplitInt on a double-float32 matrix (the TPU-native input format).

    The residual is carried as an f32 pair; two_sum keeps the value exact
    and the signed floor self-corrects hi/lo boundary off-by-ones (the
    clip pushes any ±1 overflow back into the residual, also exactly).
    """
    exp = row_exponents(m.hi)  # |lo| <= ulp(hi)/2 cannot change the row max bit
    # sign of the pair == sign of hi (lo only refines hi's last bit),
    # except hi == 0 where lo is the value.
    neg = (m.hi < 0) | ((m.hi == 0) & (m.lo < 0))
    sign = jnp.where(neg, -1, 1).astype(jnp.int8)
    a_hi = jnp.where(neg, -m.hi, m.hi)
    a_lo = jnp.where(neg, -m.lo, m.lo)
    r_hi = ldexp(a_hi, -exp[:, None]).astype(jnp.float32)
    r_lo = ldexp(a_lo, -exp[:, None]).astype(jnp.float32)
    scale = jnp.float32(2.0 ** w)

    def body(carry, _):
        r_hi, r_lo = carry
        t = r_hi * scale                   # exact
        u = r_lo * scale                   # exact
        s, e = two_sum(t, u)               # exact: s + e == t + u
        # value (s + e) >= 0 but s alone may round a hair negative; a -1
        # slice self-corrects on the next step. Clip guards the +128 edge.
        y = jnp.clip(jnp.floor(s), INT8_MIN, INT8_MAX)
        f_hi, f_e = two_sum(s, -y)         # exact for any sign/magnitude
        n_hi, t1 = two_sum(f_hi, e)
        n_lo = t1 + f_e                    # rounds at ~2^-49 of the residual
        return (n_hi, n_lo), (sign * y.astype(jnp.int8))

    _, slices = jax.lax.scan(body, (r_hi, r_lo), None, length=num_splits)
    return SplitResult(slices, exp, w)


def reconstruct(res: SplitResult, dtype=jnp.float64) -> jax.Array:
    """Sum the slices back: the kept (truncated) part of the input."""
    s = res.slices.shape[0]
    out = jnp.zeros(res.slices.shape[1:], dtype)
    for p in range(s - 1, -1, -1):
        term = ldexp(res.slices[p].astype(dtype),
                     res.exp[:, None] - (p + 1) * res.w)
        out = out + term
    return out


def split_tail(m: jax.Array, res: SplitResult) -> jax.Array:
    """Residual left uncaptured by the slices (for AUTO loss estimation)."""
    return m - reconstruct(res, m.dtype)
