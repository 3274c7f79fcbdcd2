"""Pallas TPU kernels: fused scaled accumulation of int32 slice products.

Line 7 of Algorithm 3: ``C += C_tmp ⊙ (2^{-(i+j)α} · e_A · e_B^T)``. Fusing
the int32→float conversion, the power-of-two scaling, and the add into one
VMEM pass halves the HBM traffic of the accumulation stage — which the
paper's Fig. 9 identifies as the second-largest cost of the whole scheme.

Two accumulator widths:

  * ``accum_scaled_dw``  — C in double-float32 with a compensated add
    (the TPU has no FP64 unit). 48 mantissa bits.
  * ``accum_scaled_sw``  — C in one plain word (f64 on CPU validation
    hosts). The add sequence is a single rounding, so the fused pipeline
    stays bitwise identical to the XLA ``_accum_f64`` reference path
    (power-of-two scaling commutes with rounding).

The exponent application is deferred in both: products are accumulated
against the scalar ``2^{-(t+2)w}`` only; the per-element ``e_A + e_B`` is
applied once by the caller at the end (see ``core.ozaki``). This keeps the
kernel's scale a compile-time scalar.

In/out aliasing: the C operand(s) are donated and updated in place.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.xmath import two_sum

from .launch import elementwise_blocks, grid_for, kernel_jit, pad_tail


def dw_accum_step(p, c_hi, c_lo, scale: float):
    """One fused df32 accumulation: (c_hi, c_lo) += df32(p) * scale.

    The exact rounding sequence shared by ``accum_scaled_dw`` and the
    epilogue-fused GEMM (``int8_gemm.int8_matmul_nt_epilogue_dw``) — both
    paths MUST stay bitwise identical to the XLA reference accumulation,
    so the sequence lives in exactly one place.

    Steps: exact int32 -> df32 (16-bit split; no int64 anywhere), then
    normalize (fast_two_sum) so |lo| <= ulp(hi)/2 before the compensated
    add — skipping the normalize costs ~3 decimal digits over a scheme.
    """
    low = jnp.bitwise_and(p, jnp.int32(0xFFFF))
    high = p - low
    hi_f = high.astype(jnp.float32)
    lo_f = low.astype(jnp.float32)
    n_s = hi_f + lo_f
    n_e = lo_f - (n_s - hi_f)
    t_hi = n_s * jnp.float32(scale)
    t_lo = n_e * jnp.float32(scale)
    # compensated (c_hi, c_lo) += (t_hi, t_lo)
    s_hi, e_hi = two_sum(c_hi, t_hi)
    s_lo, e_lo = two_sum(c_lo, t_lo)
    c = e_hi + s_lo
    v_hi = s_hi + c
    v_lo = c - (v_hi - s_hi)
    w = e_lo + v_lo
    n_hi = v_hi + w
    n_lo = w - (n_hi - v_hi)
    return n_hi, n_lo


def _accum_kernel(scale: float, p_ref, chi_ref, clo_ref, ohi_ref, olo_ref):
    n_hi, n_lo = dw_accum_step(p_ref[...], chi_ref[...], clo_ref[...], scale)
    ohi_ref[...] = n_hi
    olo_ref[...] = n_lo


@kernel_jit(static_argnames=("scale", "bm", "bn", "interpret"))
def accum_scaled_dw(p: jax.Array, c_hi: jax.Array, c_lo: jax.Array, *,
                    scale: float, bm: int = 256, bn: int = 256,
                    interpret: Optional[bool] = None) -> tuple[jax.Array, jax.Array]:
    """(c_hi, c_lo) += df32(p) * scale, elementwise, fused in VMEM."""
    m, n = p.shape
    bm_, bn_ = elementwise_blocks(m, n, bm, bn)
    p = pad_tail(p, (bm_, bn_))
    c_hi = pad_tail(c_hi, (bm_, bn_))
    c_lo = pad_tail(c_lo, (bm_, bn_))
    mp, np_ = p.shape
    spec = pl.BlockSpec((bm_, bn_), lambda i, j: (i, j))
    o_hi, o_lo = pl.pallas_call(
        functools.partial(_accum_kernel, scale),
        grid=grid_for((mp, np_), (bm_, bn_)),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((mp, np_), jnp.float32),
                   jax.ShapeDtypeStruct((mp, np_), jnp.float32)],
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
        name="accum_scaled_dw",
    )(p, c_hi, c_lo)
    return o_hi[:m, :n], o_lo[:m, :n]


def _accum_sw_kernel(scale: float, p_ref, c_ref, o_ref):
    c = c_ref[...]
    # int32 -> f64 is exact; scale is an exact power of two: ONE rounding.
    o_ref[...] = c + p_ref[...].astype(c.dtype) * jnp.asarray(scale, c.dtype)


@kernel_jit(static_argnames=("scale", "bm", "bn", "interpret"))
def accum_scaled_sw(p: jax.Array, c: jax.Array, *, scale: float,
                    bm: int = 256, bn: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """c += p * scale in c's (single-word) dtype, fused in VMEM.

    Used by the ``pallas_fused`` pipeline when ``accum="f64"``: the single
    rounded add per element matches the XLA reference accumulation
    bitwise, because the deferred ``ldexp(·, e_A + e_B)`` is exact.
    """
    m, n = p.shape
    bm_, bn_ = elementwise_blocks(m, n, bm, bn)
    p = pad_tail(p, (bm_, bn_))
    c = pad_tail(c, (bm_, bn_))
    mp, np_ = p.shape
    spec = pl.BlockSpec((bm_, bn_), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_accum_sw_kernel, scale),
        grid=grid_for((mp, np_), (bm_, bn_)),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), c.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="accum_scaled_sw",
    )(p, c)
    return out[:m, :n]
