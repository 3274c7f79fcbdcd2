"""The plain reference and the operand generator, against exact
arithmetic on the host."""
from fractions import Fraction

import numpy as np
import pytest

from benchmarks.chip.reference import (dd_matmul, row_exponents, scaled_error,
                                      scaled_errors)


def _exact(a, b):
    r, k = a.shape
    c = b.shape[1]
    return [[sum(Fraction(float(a[i, t])) * Fraction(float(b[t, j]))
                 for t in range(k)) for j in range(c)] for i in range(r)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dd_matmul_is_exact_to_double_double(seed):
    rng = np.random.default_rng(seed)
    a = rng.uniform(-0.5, 0.5, (3, 40)) * np.exp(rng.standard_normal((3, 40)))
    b = rng.uniform(-0.5, 0.5, (40, 4)) * np.exp(rng.standard_normal((40, 4)))
    hi, lo = dd_matmul(a, b)
    exact = _exact(a, b)
    for i in range(3):
        for j in range(4):
            got = Fraction(float(hi[i, j])) + Fraction(float(lo[i, j]))
            err = abs(got - exact[i][j])
            assert err <= abs(exact[i][j]) * Fraction(2) ** -100 + \
                Fraction(2) ** -1000


def test_dd_matmul_batches_over_leading_axes():
    rng = np.random.default_rng(3)
    a = rng.standard_normal((2, 5, 7))
    b = rng.standard_normal((2, 7, 3))
    hi, lo = dd_matmul(a, b)
    for q in range(2):
        h1, l1 = dd_matmul(a[q], b[q])
        assert np.array_equal(hi[q], h1) and np.array_equal(lo[q], l1)


def test_catastrophic_cancellation_is_kept():
    # float64 loses the small term entirely; double-double keeps it
    a = np.array([[1.0, 1e-20, -1.0]])
    b = np.array([[1.0], [1.0], [1.0]])
    hi, lo = dd_matmul(a, b)
    assert hi[0, 0] + lo[0, 0] == 1e-20


def test_row_exponents_strictly_bound_each_row():
    x = np.array([[0.5, -0.25], [3.0, 1.0], [0.0, 0.0]])
    e = row_exponents(x)
    assert e.tolist() == [0, 2, 0]
    assert np.all(np.max(np.abs(x[:2]), axis=1) < 2.0 ** e[:2])


def test_scaled_error_normalises_by_row_and_column_exponents():
    a = np.array([[3.0, 0.0], [0.0, 0.25]])      # ea = 2, -1
    b = np.array([[1.0, 0.0], [0.0, 8.0]])       # column exponents 1, 4
    ref = a @ b
    c = ref.copy()
    c[1, 1] += 2.0 ** -10
    err = scaled_error(c, ref, np.zeros_like(ref), a, b)
    assert err == 2.0 ** -10 / 2.0 ** (-1 + 4)
    c[0, 0] = np.nan
    assert scaled_error(c, ref, np.zeros_like(ref), a, b) == np.inf


def test_operands_follow_the_seed_and_carry_low_words():
    import jax
    from benchmarks.chip.operands import make_operands
    jax.config.update("jax_enable_x64", True)
    big = 2 ** 40 + 12345                      # past 32 bits
    (a, b) = make_operands(big, [(16, 24), (24, 8)], 1.0, "dw")
    (a2,) = make_operands(big, [(16, 24)], 1.0, "dw")
    (a3,) = make_operands(big + 1, [(16, 24)], 1.0, "dw")
    (a4,) = make_operands(big + 2 ** 32, [(16, 24)], 1.0, "dw")
    hi, lo = (np.asarray(x) for x in a)
    assert np.array_equal(hi, np.asarray(a2[0]))
    assert np.array_equal(lo, np.asarray(a2[1]))
    assert not np.array_equal(hi, np.asarray(a3[0]))
    assert not np.array_equal(hi, np.asarray(a4[0]))
    assert np.all(lo != 0)
    _, e = np.frexp(hi)
    assert np.all(np.abs(lo) <= np.ldexp(1.0, e - 25))     # ulp(hi) / 2
    # hi + lo is exact in float64
    v = hi.astype(np.float64) + lo.astype(np.float64)
    assert np.array_equal((v - hi.astype(np.float64)).astype(np.float32), lo)
    (f,) = make_operands(big, [(16, 24)], 1.0, "f32")
    assert np.asarray(f).dtype == np.float32


def test_scaled_errors_are_the_entries_the_largest_is_taken_over():
    rng = np.random.default_rng(5)
    a = rng.standard_normal((4, 9))
    b = rng.standard_normal((9, 3))
    hi, lo = dd_matmul(a, b)
    c = hi + rng.standard_normal(hi.shape) * 1e-12
    errs = scaled_errors(c, hi, lo, a, b)
    assert errs.shape == (4, 3) and np.all(errs > 0)
    assert float(np.max(errs)) == scaled_error(c, hi, lo, a, b)
