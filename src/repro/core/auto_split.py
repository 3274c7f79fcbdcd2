"""INT8-AUTO — automatic split-count selection (paper Sec. 4.4).

Before a GEMM, inspect the exponent distribution of both operands and pick
the smallest number of splits whose *average mantissa loss* per element is
<= a threshold ``T`` bits. ``T = 0`` keeps every input mantissa bit;
``T = 1`` admits one lost bit on average (the paper's fast mode, which
auto-selected INT8x8/9 instead of INT8x12/13 for 4.33x speedup).

The statistics pass is jitted; the split-count decision itself happens on
the host (it changes trace shapes), mirroring the paper's implementation
which inspects the matrices before dispatching the GEMM kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .splitting import row_exponents
from .xmath import exponent


@functools.partial(jax.jit, static_argnames=("w", "mantissa_bits", "max_splits"))
def _loss_curve(m: jax.Array, w: int, mantissa_bits: int,
                max_splits: int) -> jax.Array:
    """Mean lost mantissa bits per element for s = 1..max_splits.

    An element with exponent e under a row exponent E keeps bits down to
    E - s*w; its own mantissa reaches e - mantissa_bits. Loss is the gap,
    clipped to [0, mantissa_bits]. Zeros lose nothing.
    """
    row_e = row_exponents(m)[:, None]
    elem_e = exponent(m)
    nonzero = m != 0
    losses = []
    for s in range(1, max_splits + 1):
        floor_bit = row_e - s * w
        lowest_bit = elem_e - mantissa_bits
        loss = jnp.clip(floor_bit - lowest_bit, 0, mantissa_bits)
        loss = jnp.where(nonzero, loss, 0)
        losses.append(jnp.mean(loss.astype(jnp.float32)))
    return jnp.stack(losses)


def auto_num_splits(a: jax.Array, b: jax.Array, w: int, *,
                    threshold_bits: float = 0.0, mantissa_bits: int = 53,
                    max_splits: int = 26) -> int:
    """Smallest s with mean mantissa loss <= threshold for BOTH operands."""
    curve_a = np.asarray(_loss_curve(a, w, mantissa_bits, max_splits))
    curve_b = np.asarray(_loss_curve(b.T, w, mantissa_bits, max_splits))
    curve = np.maximum(curve_a, curve_b)
    ok = np.nonzero(curve <= threshold_bits)[0]
    if ok.size == 0:
        return max_splits
    return int(ok[0]) + 1


def auto_num_splits_complex(a: jax.Array, b: jax.Array, w: int, *,
                            threshold_bits: float = 0.0,
                            mantissa_bits: int = 53,
                            max_splits: int = 26) -> int:
    """AUTO over the 4 real component matrices of a complex GEMM."""
    s = 1
    for x, transpose in ((jnp.real(a), False), (jnp.imag(a), False),
                         (jnp.real(b), True), (jnp.imag(b), True)):
        xm = x.T if transpose else x
        curve = np.asarray(_loss_curve(xm, w, mantissa_bits, max_splits))
        ok = np.nonzero(curve <= threshold_bits)[0]
        s = max(s, (int(ok[0]) + 1) if ok.size else max_splits)
    return s
