"""Pallas TPU kernel: fused one-pass SplitInt (beyond-paper optimization O3).

Algorithm 4 as literally written re-reads the residual matrix once per
split — ``s`` HBM round-trips. This kernel reads each input tile ONCE into
VMEM and emits all ``s`` int8 slices from registers, turning the split
stage from ``s``-pass to 1-pass (the split stage is memory-bound; see the
paper's Fig. 9 breakdown).

Input is a double-word pair (hi, lo) plus the precomputed per-row exponent
vector. The arithmetic is dtype-generic: the TPU deployment feeds the
native df32 pair, while the FP64 entry point (``core.ozaki`` with
``backend="pallas_fused"`` and f64 operands) passes ``(a, 0.0)`` — with a
zero low word the two_sum chain degenerates to exactly Algorithm 4's
sign-magnitude extraction, so the slices are bitwise identical to
``core.splitting.split_int``. Output block is (s, bm, bk) int8 — for
s = 13, bm = bk = 256 that is 852 KiB VMEM, well inside budget.

Validated on CPU in interpret mode against ``repro.core.splitting``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.xmath import two_sum

from .launch import grid_for, int8_tile_blocks, kernel_jit, pad_tail


def split_tile(out_ref, hi, lo, exp, num_splits: int, w: int):
    """Emit ``num_splits`` int8 slices of a (bm, bk) tile into ``out_ref``.

    ``exp`` is the (bm, 1) int32 column of full-row exponents. The
    extraction is elementwise per (row, col) given that exponent, so any
    tiling of the operand produces bitwise-identical slices — the
    streaming GEMM kernels call this on VMEM scratch refs with the same
    guarantee as the standalone split pass. The slice chain is
    prefix-stable: the first p slices do not depend on how many more will
    be extracted, so callers may size ``num_splits`` down to just the
    prefix they consume.

    Mosaic-legal arithmetic only: the sign is applied to the float slice
    value (exact), and the int8 narrowing happens once, at the store —
    the TPU VPU has no int8 multiply, and no int64 reaches the kernel.
    """
    neg = (hi < 0) | ((hi == 0) & (lo < 0))
    a_hi = jnp.where(neg, -hi, hi)
    a_lo = jnp.where(neg, -lo, lo)
    # ldexp is exact (XLA's exp2 is not, even at integer arguments); the
    # scaled residual lands in [0, 1) like Algorithm 4 requires.
    r_hi = jnp.ldexp(a_hi, -exp)
    r_lo = jnp.ldexp(a_lo, -exp)
    scale = jnp.asarray(2.0 ** w, hi.dtype)

    # a loop, not a Python unroll: XLA:CPU takes minutes to compile the
    # unrolled f32 chain at s >= 9 in interpret mode
    def body(p, carry):
        r_hi, r_lo = carry
        t = r_hi * scale
        u = r_lo * scale
        s, e = two_sum(t, u)
        y = jnp.clip(jnp.floor(s), -128, 127)
        f_hi, f_e = two_sum(s, -y)
        r_hi, t1 = two_sum(f_hi, e)
        out_ref[p] = jnp.where(neg, -y, y).astype(jnp.int32).astype(jnp.int8)
        return r_hi, t1 + f_e

    jax.lax.fori_loop(0, num_splits, body, (r_hi, r_lo))


def _split_kernel(num_splits: int, w: int, hi_ref, lo_ref, exp_ref, out_ref):
    split_tile(out_ref, hi_ref[...], lo_ref[...], exp_ref[...],
               num_splits, w)


@kernel_jit(static_argnames=("num_splits", "w", "bm", "bk", "interpret"))
def fused_split_dw(hi: jax.Array, lo: jax.Array, exp: jax.Array, *,
                   num_splits: int, w: int, bm: int = 256, bk: int = 256,
                   interpret: Optional[bool] = None) -> jax.Array:
    """All-slices-in-one-pass SplitInt. Returns (s, m, k) int8."""
    m, k = hi.shape
    # bm is the second-to-last dim of the int8 OUTPUT block: 32-sublane.
    bm_, bk_ = int8_tile_blocks(m, k, bm, bk)
    hi = pad_tail(hi, (bm_, bk_))
    lo = pad_tail(lo, (bm_, bk_))
    exp = pad_tail(exp[:, None], (bm_, 1))
    mp, kp = hi.shape
    out = pl.pallas_call(
        functools.partial(_split_kernel, num_splits, w),
        grid=grid_for((mp, kp), (bm_, bk_)),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j: (i, j)),
            pl.BlockSpec((bm_, bk_), lambda i, j: (i, j)),
            pl.BlockSpec((bm_, 1), lambda i, j: (i, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((num_splits, bm_, bk_),
                               lambda i, j: (jnp.int32(0), i, j)),
        out_shape=jax.ShapeDtypeStruct((num_splits, mp, kp), jnp.int8),
        interpret=interpret,
        name="fused_split_dw",
    )(hi, lo, exp)
    return out[:, :m, :k]
