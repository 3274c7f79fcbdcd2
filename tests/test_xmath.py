"""Error-free transform exactness — verified against exact rationals."""
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis "
                           "(pip install -r requirements-test.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.xmath import (DW, dd_matmul_f64, dd_matmul_np, df32_from_f64,
                              df32_to_f64, dw_add, dw_mul, dw_to_single,
                              fast_two_sum, rel_error_vs_dd, two_prod,
                              two_sum, veltkamp_split)

# XLA:CPU flushes subnormals to zero -> keep magnitudes in normal range
finite = st.floats(allow_nan=False, allow_infinity=False,
                   allow_subnormal=False,
                   min_value=-1e30, max_value=1e30).filter(
                       lambda x: x == 0.0 or abs(x) > 1e-200)


@given(finite, finite)
@settings(max_examples=300, deadline=None)
def test_two_sum_exact(a, b):
    s, e = (np.asarray(x) for x in two_sum(jnp.float64(a), jnp.float64(b)))
    assert Fraction(float(s)) + Fraction(float(e)) == \
        Fraction(a) + Fraction(b)


prod_floats = st.floats(allow_nan=False, allow_infinity=False,
                        allow_subnormal=False, min_value=-1e100,
                        max_value=1e100).filter(
                            lambda x: x == 0.0 or abs(x) > 1e-120)


@given(prod_floats, prod_floats)
@settings(max_examples=300, deadline=None)
def test_two_prod_exact(a, b):
    p, e = (np.asarray(x) for x in two_prod(jnp.float64(a), jnp.float64(b)))
    if np.isfinite(p) and np.isfinite(e):
        assert Fraction(float(p)) + Fraction(float(e)) == \
            Fraction(a) * Fraction(b)


@given(finite)
@settings(max_examples=200, deadline=None)
def test_veltkamp_split_exact(a):
    hi, lo = (np.asarray(x) for x in veltkamp_split(jnp.float64(a)))
    assert float(hi) + float(lo) == a
    # halves fit in 26/27 bits -> their product is exact in f64
    assert float(np.float64(hi) * np.float64(hi)) == float(hi) ** 2


@given(finite, finite)
@settings(max_examples=200, deadline=None)
def test_fast_two_sum_when_ordered(a, b):
    if abs(a) < abs(b):
        a, b = b, a
    s, e = (np.asarray(x) for x in
            fast_two_sum(jnp.float64(a), jnp.float64(b)))
    assert Fraction(float(s)) + Fraction(float(e)) == \
        Fraction(a) + Fraction(b)


@given(finite, finite, finite, finite)
@settings(max_examples=100, deadline=None)
def test_dw_add_high_accuracy(a_hi, a_lo, b_hi, b_lo):
    # normalize into VALID double-word pairs first (|lo| <= ulp(hi)/2)
    ah, al = two_sum(jnp.float64(a_hi), jnp.float64(a_lo * 1e-18))
    bh, bl = two_sum(jnp.float64(b_hi), jnp.float64(b_lo * 1e-18))
    a = DW(ah, al)
    b = DW(bh, bl)
    out = dw_add(a, b)
    exact = (Fraction(float(a.hi)) + Fraction(float(a.lo))
             + Fraction(float(b.hi)) + Fraction(float(b.lo)))
    got = Fraction(float(out.hi)) + Fraction(float(out.lo))
    if exact != 0:
        rel = abs((got - exact) / exact)
        assert rel < Fraction(1, 2 ** 100)


def test_df32_roundtrip():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.uniform(-1, 1, 128))
    dw = df32_from_f64(x)
    back = np.asarray(df32_to_f64(dw))
    # 48-bit mantissa: relative error < 2^-47
    np.testing.assert_allclose(back, np.asarray(x), rtol=2 ** -47)


def test_dd_matmul_agrees_with_np_oracle(rng):
    a = jnp.asarray(rng.uniform(-1, 1, (17, 23)))
    b = jnp.asarray(rng.uniform(-1, 1, (23, 9)))
    dw = dd_matmul_f64(a, b)
    hi, lo = dd_matmul_np(np.asarray(a), np.asarray(b))
    # both are valid dd oracles; XLA vs numpy rounding paths may differ
    # in the last ulp of the compensated term
    np.testing.assert_allclose(np.asarray(dw.hi), hi, rtol=0, atol=5e-16)
    np.testing.assert_allclose(np.asarray(dw.hi) + np.asarray(dw.lo),
                               hi + lo, rtol=0, atol=5e-16)


def test_dd_matmul_beats_plain_f64(rng):
    # cancellation-heavy case: dd must be closer to the exact value.
    # numpy oracle: XLA:CPU contracts mul+add into FMA inside scans,
    # which perturbs Dekker's two_prod there (the jax dd path is still
    # <= plain-f64 error; the np oracle is the reference used by all
    # accuracy benchmarks).
    an_ = rng.uniform(-1, 1, (8, 64))
    bn_ = rng.uniform(-1, 1, (64, 8))
    hi_, lo_ = dd_matmul_np(an_, bn_)
    import collections
    dw = collections.namedtuple('R', 'hi lo')(hi_, lo_)
    a, b = jnp.asarray(an_), jnp.asarray(bn_)
    exact = np.zeros((8, 8), object)
    an, bn = np.asarray(a), np.asarray(b)
    for i in range(8):
        for j in range(8):
            exact[i, j] = sum(Fraction(an[i, t]) * Fraction(bn[t, j])
                              for t in range(64))
    dd_err = plain_err = 0.0
    plain = an @ bn
    for i in range(8):
        for j in range(8):
            got = Fraction(float(dw.hi[i, j])) + Fraction(float(dw.lo[i, j]))
            dd_err = max(dd_err, abs(float(got - exact[i, j])))
            plain_err = max(plain_err,
                            abs(float(Fraction(plain[i, j])
                                      - exact[i, j])))
    assert dd_err <= plain_err
    assert dd_err < 1e-20


def test_rel_error_vs_dd_zero_safe():
    c = np.array([[1.0, 0.0]])
    err = rel_error_vs_dd(c, np.array([[1.0, 0.0]]), np.zeros((1, 2)))
    assert np.all(err == 0)


def _xmath_case(name):
    """(x, n) pairs the pipeline feeds ``xmath.exponent``/``ldexp``."""
    from repro.core.splitting import ZERO_ROW_EXP
    rng = np.random.default_rng(11)
    k = np.arange(-1022, 1024)
    if name == "zeros":
        return np.array([0.0, -0.0, 0.0, -0.0]), np.array([0, 7, -60, 2047])
    if name == "powers_of_two":
        x = np.concatenate([np.ldexp(1.0, k), -np.ldexp(1.0, k)])
        return x, rng.integers(-60, 61, x.shape)
    if name == "zero_row_exp":
        # an all-zero row is split against the finite sentinel exponent
        return np.zeros(64), np.full(64, -ZERO_ROW_EXP)
    if name == "phi2":
        # paper Eq. 6 at phi = 2: the widest magnitude spread it uses
        x = (rng.uniform(-0.5, 0.5, 4096)
             * np.exp(2.0 * rng.standard_normal(4096)))
        return x, -rng.integers(-40, 41, x.shape)
    if name == "extreme_row_exponents":
        # the largest and smallest normal rows, and the int32 slice
        # products scaled by 2^(ea + eb - (t + 2) w) at both ends
        big = np.nextafter(np.ldexp(1.0, k[-8:]), np.inf)
        x = np.concatenate([np.ldexp(1.0, [-1022, -1021, 1023]),
                            [np.finfo(np.float64).max], big, -big,
                            rng.integers(-2 ** 31, 2 ** 31, 64) * 1.0])
        n = np.concatenate([[1021, 1021, -1024, -1024],
                            np.full(16, -1024),
                            rng.integers(-2146, 2049, 64)])
        return x, n
    raise AssertionError(name)


@pytest.mark.parametrize("name", ["zeros", "powers_of_two", "zero_row_exp",
                                  "phi2", "extreme_row_exponents"])
def test_exponent_and_ldexp_bitwise_match_jnp(name):
    """``xmath.exponent``/``xmath.ldexp`` build f64 exponents and scales
    without a 64-bit bitcast (XLA:TPU has none); on the inputs the
    pipeline feeds them they are bitwise ``jnp.frexp``/``jnp.ldexp``."""
    from repro.core.xmath import exponent, ldexp
    x, n = _xmath_case(name)
    x = jnp.asarray(x, jnp.float64)
    n = jnp.asarray(n, jnp.int32)
    np.testing.assert_array_equal(np.asarray(jax.jit(exponent)(x)),
                                  np.asarray(jnp.frexp(x)[1]))
    got = np.asarray(jax.jit(ldexp)(x, n))
    want = np.asarray(jnp.ldexp(x, n))
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
    # f32 goes straight to jnp: the df32 path is unchanged
    x32 = x.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(exponent(x32)),
                                  np.asarray(jnp.frexp(x32)[1]))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_f64_routes_refused_only_on_tpu(monkeypatch, backend):
    """XLA:TPU float64 is not IEEE binary64, so f64 operands are refused
    there with an error naming the DW route; elsewhere they run. The DW
    route runs on both."""
    import repro
    from repro.core.xmath import DW
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    a = jnp.asarray(np.random.default_rng(5).standard_normal((8, 16)))
    b = a.T
    for spec in ("ozaki-fp64x3", "ozaki2-fp64"):
        if backend == "tpu":
            with pytest.raises(TypeError, match=r"refused on TPU.*DW"):
                repro.matmul(a, b, precision=spec)
        else:
            assert np.isfinite(np.asarray(
                repro.matmul(a, b, precision=spec))).all()
    dw = repro.matmul(df32_from_f64(a), df32_from_f64(b),
                      precision="ozaki-fp64x9")
    assert isinstance(dw, DW)
    ref = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(df32_to_f64(dw)), ref, rtol=0,
                               atol=1e-12 * np.abs(ref).max())
