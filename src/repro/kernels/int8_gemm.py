"""Pallas TPU kernel: int8 x int8 -> int32 GEMM on the MXU (NT layout).

Computes ``C[m, n] = sum_k A[m, k] * B[n, k]`` — both operands contract on
their last axis, which is exactly how the Ozaki scheme stores B slices
(column-split of B == row-split of B^T), and is the MXU-friendly layout:
no transposition between HBM and VMEM.

Tiling: grid (m/bm, n/bn, k/bk), k innermost so each output block stays
resident in VMEM while the k loop streams A/B tiles through the MXU,
accumulating in int32. With no block requested, ``int8_matmul_nt`` runs
``launch.GEMM_BLOCKS``, MXU-aligned 256x256x512: A and B tiles of 128 KiB
and a 256 KiB int32 C tile, about 1 MiB with double buffering. The
epilogue-fused variants size their blocks from a VMEM working-set model
instead (``launch.epilogue_blocks``): at 16384^2 outputs a 1024x1024 C
tile with its two df32 planes, in and out and double-buffered, is 32 MiB,
so those launches declare a scoped VMEM limit above Mosaic's default.

``int8_matmul_nt_batched`` adds a leading batch grid dimension — one
kernel launch for a whole ``(B, m, k) x (B, n, k)`` stack (the batched
Ozaki API's fully-batched case); the per-(batch, m, n) k-loop is
unchanged. Launch bookkeeping (block shrink, padding, grid) comes from
the shared ``launch`` layer.

``int8_matmul_nt_epilogue_{sw,dw}`` are the epilogue-fused variants used
by the ``fusion="epilogue"`` executor: the int32 slice products of one
anti-diagonal group accumulate in a VMEM scratch block across a
(pairs, k) grid walk and are folded into the carried high-precision
accumulator C inside the GEMM grid's epilogue — the int32 products never
round-trip to HBM (see ``core.tuning.hbm_pass_model``). The epilogue
runs the exact rounding sequence of the standalone accumulation kernels
(``ozaki_accum.dw_accum_step`` / the single rounded f64 add), so results
stay bitwise identical to the ``xla`` reference pipeline. Both epilogue
variants also take batch-grid operands — ``(s, B, m, k)`` slice stacks
with ``(B, m, n)`` carried accumulators and the batch as the outermost
grid dimension — so stacked-weights batches keep epilogue fusion.

Validated on CPU in interpret mode against ``ref.int8_matmul_nt_ref``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .launch import (crt_blocks, epilogue_blocks, epilogue_vmem_limit,
                     gemm_blocks, grid_for, kernel_jit, pad_tail,
                     streaming_blocks)
from .ozaki_accum import dw_accum_step
from .ozaki_split import split_tile


def _kernel(a_ref, b_ref, o_ref):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    prod = jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    o_ref[...] += prod


@kernel_jit(static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul_nt(a: jax.Array, b_t: jax.Array, *, bm: Optional[int] = None,
                   bn: Optional[int] = None, bk: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """C = A @ B_t.T with int32 accumulation. a: (m, k) int8, b_t: (n, k)."""
    assert a.dtype == jnp.int8 and b_t.dtype == jnp.int8
    m, k = a.shape
    n, k2 = b_t.shape
    assert k == k2, (a.shape, b_t.shape)
    bm_, bn_, bk_ = gemm_blocks(m, n, k, bm, bn, bk)
    a_p = pad_tail(a, (bm_, bk_))
    b_p = pad_tail(b_t, (bn_, bk_))
    mp, kp = a_p.shape
    np_, _ = b_p.shape
    grid = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn_, bk_), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
        name="int8_matmul_nt",
    )(a_p, b_p)
    return out[:m, :n]


def _kernel_batched(a_ref, b_ref, o_ref):
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    prod = jax.lax.dot_general(
        a_ref[0], b_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    o_ref[...] += prod[None]


@kernel_jit(static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul_nt_batched(a: jax.Array, b_t: jax.Array, *,
                           bm: Optional[int] = None,
                           bn: Optional[int] = None,
                           bk: Optional[int] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """C[b] = A[b] @ B_t[b].T for every batch row, one kernel launch.

    a: (B, m, k) int8, b_t: (B, n, k) int8 -> (B, m, n) int32. The batch
    is the outermost grid dimension, so consecutive program instances
    reuse the same (i, j, k) walk per batch row.
    """
    assert a.dtype == jnp.int8 and b_t.dtype == jnp.int8
    B, m, k = a.shape
    B2, n, k2 = b_t.shape
    assert B == B2 and k == k2, (a.shape, b_t.shape)
    bm_, bn_, bk_ = gemm_blocks(m, n, k, bm, bn, bk)
    a_p = pad_tail(a, (bm_, bk_))
    b_p = pad_tail(b_t, (bn_, bk_))
    _, mp, kp = a_p.shape
    _, np_, _ = b_p.shape
    grid = (B,) + grid_for((mp, np_, kp), (bm_, bn_, bk_))
    out = pl.pallas_call(
        _kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda b, i, j, kk: (b, i, kk)),
            pl.BlockSpec((1, bn_, bk_), lambda b, i, j, kk: (b, j, kk)),
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, mp, np_), jnp.int32),
        interpret=interpret,
        name="int8_matmul_nt_batched",
    )(a_p, b_p)
    return out[:, :m, :n]


# ----------------------------------------------------------------------------
# Epilogue-fused variants: GEMM + scaled high-precision accumulation in one
# launch. One call per anti-diagonal group; the int32 group product lives
# only in a VMEM scratch block.
# ----------------------------------------------------------------------------
#
# Grid is (m/bm, n/bn, npairs, k/bk) with the C block index a function of
# (i, j) only, so for each output block the whole (pairs, k) walk happens
# while C stays resident. Slice operands are indexed affinely in the pair
# dimension: A uses slice ``p_lo + pp``, B uses ``t - p_lo - pp`` — exactly
# the anti-diagonal's (p, q = t - p) pairs. The int32 scratch accumulator
# is exact (alpha reserves diagonal-fusion headroom), so the epilogue sees
# the same group product P_t the unfused pipeline materializes to HBM.
#
# The batch-grid variants take (s, B, m, k) x (s, B, n, k) slice stacks
# and prepend the batch as the OUTERMOST grid dimension:
# (B, m/bm, n/bn, npairs, k/bk). The inner (pairs, k) walk per C block is
# unchanged — the scratch accumulator carries across grid steps exactly
# as in the 2-D kernel because (pp, kk) remain the fastest-varying dims —
# so a stacked-weights batch keeps ``fuse_epilogue=True`` instead of
# falling back to the stage-fused pipeline (the PR 2 limitation).


# The kernel bodies run in pieces, in loops: the product over K in chunks
# of at most EPILOGUE_K_CHUNK, the epilogue over strips of at most
# EPILOGUE_ROW_STRIP rows. Each of a call's nine launches compiles to its
# own kernel, whose code sits in HBM beside the data; unrolled over a
# 1024 x 1024 x 4096 block it was 3.6 MB a launch (compiled for a v5e),
# in pieces 0.65 MB, whatever the blocks.
EPILOGUE_K_CHUNK = 512
EPILOGUE_ROW_STRIP = 128


def _in_pieces(extent: int, cap: int, body) -> None:
    """``body(ds)`` over ``extent`` in pieces of the largest power of two
    that divides it, at most ``cap``."""
    size = math.gcd(extent, cap)

    def piece(i, carry):
        body(pl.ds(pl.multiple_of(i * size, size), size))
        return carry

    # int32 bounds: an int64 index (x64 on) fails Mosaic's verifier
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(extent // size), piece, 0)


def _accumulate(lead, a_ref, b_ref, acc_ref, first):
    """acc += A @ B.T over this block's K, one chunk at a time, from zero
    at the ``first`` grid step; ``lead`` indexes the operand blocks'
    leading (pair, batch) dims."""

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(ks):
        acc_ref[...] += jax.lax.dot_general(
            a_ref[lead + (slice(None), ks)], b_ref[lead + (slice(None), ks)],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)

    _in_pieces(a_ref.shape[-1], EPILOGUE_K_CHUNK, chunk)


def _epilogue_kernel_sw(scale, npairs, nk, a_ref, b_ref, c_ref, o_ref,
                        acc_ref):
    pp = pl.program_id(2)
    kk = pl.program_id(3)
    _accumulate((0,), a_ref, b_ref, acc_ref, (pp == 0) & (kk == 0))

    @pl.when((pp == npairs - 1) & (kk == nk - 1))
    def _epilogue():
        def strip(rows):
            c = c_ref[rows, :]
            # int32 -> f64 exact; scale an exact power of two: ONE
            # rounding, matching ``_accum_f64`` / ``accum_scaled_sw``
            # bitwise.
            o_ref[rows, :] = c + acc_ref[rows, :].astype(c.dtype) * \
                jnp.asarray(scale, c.dtype)

        _in_pieces(acc_ref.shape[0], EPILOGUE_ROW_STRIP, strip)


def _epilogue_kernel_dw(scale, npairs, nk, a_ref, b_ref, chi_ref, clo_ref,
                        ohi_ref, olo_ref, acc_ref):
    pp = pl.program_id(2)
    kk = pl.program_id(3)
    _accumulate((0,), a_ref, b_ref, acc_ref, (pp == 0) & (kk == 0))

    @pl.when((pp == npairs - 1) & (kk == nk - 1))
    def _epilogue():
        def strip(rows):
            n_hi, n_lo = dw_accum_step(acc_ref[rows, :], chi_ref[rows, :],
                                       clo_ref[rows, :], scale)
            ohi_ref[rows, :] = n_hi
            olo_ref[rows, :] = n_lo

        _in_pieces(acc_ref.shape[0], EPILOGUE_ROW_STRIP, strip)


def _epilogue_kernel_batched_sw(scale, npairs, nk, a_ref, b_ref, c_ref,
                                o_ref, acc_ref):
    pp = pl.program_id(3)
    kk = pl.program_id(4)
    _accumulate((0, 0), a_ref, b_ref, acc_ref, (pp == 0) & (kk == 0))

    @pl.when((pp == npairs - 1) & (kk == nk - 1))
    def _epilogue():
        def strip(rows):
            c = c_ref[0, rows, :]
            o_ref[0, rows, :] = c + acc_ref[rows, :].astype(c.dtype) * \
                jnp.asarray(scale, c.dtype)

        _in_pieces(acc_ref.shape[0], EPILOGUE_ROW_STRIP, strip)


def _epilogue_kernel_batched_dw(scale, npairs, nk, a_ref, b_ref, chi_ref,
                                clo_ref, ohi_ref, olo_ref, acc_ref):
    pp = pl.program_id(3)
    kk = pl.program_id(4)
    _accumulate((0, 0), a_ref, b_ref, acc_ref, (pp == 0) & (kk == 0))

    @pl.when((pp == npairs - 1) & (kk == nk - 1))
    def _epilogue():
        def strip(rows):
            n_hi, n_lo = dw_accum_step(acc_ref[rows, :], chi_ref[0, rows, :],
                                       clo_ref[0, rows, :], scale)
            ohi_ref[0, rows, :] = n_hi
            olo_ref[0, rows, :] = n_lo

        _in_pieces(acc_ref.shape[0], EPILOGUE_ROW_STRIP, strip)


_EPILOGUE_BATCHED = {_epilogue_kernel_sw: _epilogue_kernel_batched_sw,
                     _epilogue_kernel_dw: _epilogue_kernel_batched_dw}


def epilogue_grid(a_shape, b_shape, npairs: int, *, planes: int,
                  el_bytes: int, bm: Optional[int] = None,
                  bn: Optional[int] = None, bk: Optional[int] = None):
    """(blocks, grid) of one epilogue launch over (s, m, k) x (s, n, k)
    slice stacks carrying ``planes`` accumulator planes of ``el_bytes``
    each: blocks from ``launch.epilogue_blocks``, grid (m/bm, n/bn,
    npairs, k/bk), with the batch first for (s, B, m, k) stacks."""
    *batch, m, k = a_shape[1:]
    n = b_shape[-2]
    blocks = epilogue_blocks(m, n, k, bm, bn, bk, planes=planes,
                             el_bytes=el_bytes)
    gm, gn, gk = (-(-d // b) for d, b in zip((m, n, k), blocks))
    return blocks, (*batch, gm, gn, npairs, gk)


def _epilogue_params(blocks, c_arrays):
    """Mosaic parameters of an epilogue launch: the scoped VMEM limit its
    blocks were sized to, where that is above the default."""
    limit = epilogue_vmem_limit(*blocks, planes=len(c_arrays),
                                el_bytes=c_arrays[0].dtype.itemsize)
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


def _epilogue_launch(a_slices, b_slices, c_arrays, kernel, *, p_lo, t,
                     npairs, scale, bm, bn, bk, interpret, name):
    """Shared launch recipe for both epilogue variants, 2-D and batched.

    c_arrays: list of (m, n) — or (B, m, n) for (s, B, m, k) slice
    stacks — accumulator planes (1 for sw, 2 for dw), donated and
    carried through ``input_output_aliases``.
    """
    if a_slices.ndim == 4:
        return _epilogue_launch_batched(
            a_slices, b_slices, c_arrays, _EPILOGUE_BATCHED[kernel],
            p_lo=p_lo, t=t, npairs=npairs, scale=scale, bm=bm, bn=bn,
            bk=bk, interpret=interpret, name=name)
    s, m, k = a_slices.shape
    s2, n, k2 = b_slices.shape
    assert k == k2, (a_slices.shape, b_slices.shape)
    assert 0 <= p_lo and p_lo + npairs <= s, (p_lo, npairs, s)
    assert 0 <= t - p_lo - (npairs - 1) and t - p_lo < s2, (p_lo, t, npairs)
    blocks, _ = epilogue_grid(a_slices.shape, b_slices.shape, npairs,
                              planes=len(c_arrays),
                              el_bytes=c_arrays[0].dtype.itemsize, bm=bm,
                              bn=bn, bk=bk)
    bm_, bn_, bk_ = blocks
    a_p = pad_tail(a_slices, (bm_, bk_))
    b_p = pad_tail(b_slices, (bn_, bk_))
    c_p = [pad_tail(c, (bm_, bn_)) for c in c_arrays]
    _, mp, kp = a_p.shape
    _, np_, _ = b_p.shape
    gm, gn, gk = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    nc = len(c_p)
    c_spec = pl.BlockSpec((bm_, bn_), lambda i, j, pp, kk: (i, j))
    outs = pl.pallas_call(
        functools.partial(kernel, scale, npairs, gk),
        grid=(gm, gn, npairs, gk),
        in_specs=[
            pl.BlockSpec((1, bm_, bk_),
                         lambda i, j, pp, kk: (p_lo + pp, i, kk)),
            pl.BlockSpec((1, bn_, bk_),
                         lambda i, j, pp, kk: (t - p_lo - pp, j, kk)),
        ] + [c_spec] * nc,
        out_specs=[c_spec] * nc,
        out_shape=[jax.ShapeDtypeStruct((mp, np_), c.dtype) for c in c_p],
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        input_output_aliases={2 + i: i for i in range(nc)},
        compiler_params=_epilogue_params(blocks, c_arrays),
        interpret=interpret,
        name=name,
    )(a_p, b_p, *c_p)
    return [o[:m, :n] for o in outs]


def _epilogue_launch_batched(a_slices, b_slices, c_arrays, kernel, *, p_lo,
                             t, npairs, scale, bm, bn, bk, interpret, name):
    """Batch-grid epilogue launch: (s, B, m, k) x (s, B, n, k) slices,
    (B, m, n) carried accumulators, batch outermost in the grid."""
    s, B, m, k = a_slices.shape
    s2, B2, n, k2 = b_slices.shape
    assert k == k2 and B == B2, (a_slices.shape, b_slices.shape)
    assert 0 <= p_lo and p_lo + npairs <= s, (p_lo, npairs, s)
    assert 0 <= t - p_lo - (npairs - 1) and t - p_lo < s2, (p_lo, t, npairs)
    blocks, _ = epilogue_grid(a_slices.shape, b_slices.shape, npairs,
                              planes=len(c_arrays),
                              el_bytes=c_arrays[0].dtype.itemsize, bm=bm,
                              bn=bn, bk=bk)
    bm_, bn_, bk_ = blocks
    a_p = pad_tail(a_slices, (bm_, bk_))
    b_p = pad_tail(b_slices, (bn_, bk_))
    c_p = [pad_tail(c, (bm_, bn_)) for c in c_arrays]
    _, _, mp, kp = a_p.shape
    _, _, np_, _ = b_p.shape
    gm, gn, gk = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    nc = len(c_p)
    c_spec = pl.BlockSpec((1, bm_, bn_), lambda b, i, j, pp, kk: (b, i, j))
    outs = pl.pallas_call(
        functools.partial(kernel, scale, npairs, gk),
        grid=(B, gm, gn, npairs, gk),
        in_specs=[
            pl.BlockSpec((1, 1, bm_, bk_),
                         lambda b, i, j, pp, kk: (p_lo + pp, b, i, kk)),
            pl.BlockSpec((1, 1, bn_, bk_),
                         lambda b, i, j, pp, kk: (t - p_lo - pp, b, j, kk)),
        ] + [c_spec] * nc,
        out_specs=[c_spec] * nc,
        out_shape=[jax.ShapeDtypeStruct((B, mp, np_), c.dtype) for c in c_p],
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        input_output_aliases={2 + i: i for i in range(nc)},
        compiler_params=_epilogue_params(blocks, c_arrays),
        interpret=interpret,
        name=name,
    )(a_p, b_p, *c_p)
    return [o[:, :m, :n] for o in outs]


@kernel_jit(static_argnames=("p_lo", "t", "npairs", "scale", "bm", "bn",
                             "bk", "interpret"))
def int8_matmul_nt_epilogue_sw(a_slices: jax.Array, b_slices: jax.Array,
                               c: jax.Array, *, p_lo: int, t: int,
                               npairs: int, scale: float,
                               bm: Optional[int] = None,
                               bn: Optional[int] = None,
                               bk: Optional[int] = None,
                               interpret: Optional[bool] = None) -> jax.Array:
    """c += (sum_pp A[p_lo+pp] @ B[t-p_lo-pp].T) * scale, epilogue-fused.

    a_slices: (s, m, k) int8; b_slices: (s, n, k) int8; c: (m, n) float
    (f64 on CPU oracle hosts). One launch covers one anti-diagonal group.
    Batch-grid form: (s, B, m, k) x (s, B, n, k) slices with a (B, m, n)
    accumulator — the batch rides as the outermost grid dimension.
    """
    assert a_slices.dtype == jnp.int8 and b_slices.dtype == jnp.int8
    (out,) = _epilogue_launch(a_slices, b_slices, [c], _epilogue_kernel_sw,
                              p_lo=p_lo, t=t, npairs=npairs, scale=scale,
                              bm=bm, bn=bn, bk=bk, interpret=interpret,
                              name="int8_matmul_nt_epilogue_sw")
    return out


@kernel_jit(static_argnames=("p_lo", "t", "npairs", "scale", "bm", "bn",
                             "bk", "interpret"))
def int8_matmul_nt_epilogue_dw(a_slices: jax.Array, b_slices: jax.Array,
                               c_hi: jax.Array, c_lo: jax.Array, *,
                               p_lo: int, t: int, npairs: int, scale: float,
                               bm: Optional[int] = None,
                               bn: Optional[int] = None,
                               bk: Optional[int] = None,
                               interpret: Optional[bool] = None
                               ) -> tuple[jax.Array, jax.Array]:
    """(c_hi, c_lo) += df32(group product) * scale, epilogue-fused.

    The compensated df32 add is ``ozaki_accum.dw_accum_step`` — the same
    rounding sequence as the standalone fused accumulation kernel, so the
    epilogue pipeline stays bitwise identical to the XLA reference.
    Accepts the batch-grid form exactly like the sw variant: (s, B, m, k)
    slices with (B, m, n) accumulator planes.
    """
    assert a_slices.dtype == jnp.int8 and b_slices.dtype == jnp.int8
    o_hi, o_lo = _epilogue_launch(a_slices, b_slices, [c_hi, c_lo],
                                  _epilogue_kernel_dw, p_lo=p_lo, t=t,
                                  npairs=npairs, scale=scale, bm=bm, bn=bn,
                                  bk=bk, interpret=interpret,
                                  name="int8_matmul_nt_epilogue_dw")
    return o_hi, o_lo


# ----------------------------------------------------------------------------
# Fused-CRT variants (Ozaki Scheme II): residue GEMMs + balanced-Garner
# reconstruction in one launch. The int32 residue products accumulate in a
# (ell, bm, bn) VMEM scratch stack across a (modulus, k) grid walk and the
# CRT epilogue reconstructs the f64 value in-register at the last grid
# step — the per-modulus int32 product planes never round-trip to HBM.
# ----------------------------------------------------------------------------
#
# Grid is (m/bm, n/bn, ell, k/bk) with the C block index a function of
# (i, j) only, so for each output block the whole (modulus, k) walk
# happens while the accumulator stack stays resident. The epilogue replays
# ``core.modular.crt_digits``/``crt_value`` exactly: centered residues per
# modulus, Garner's int32 recurrence with host-baked constants (every
# intermediate bounded by ~125 + ell*125*250 < 2^21 — the centering step
# is what makes that bound hold in here too), then the f64 sum smallest
# radix first with the same python-float scales. Integer stages are exact
# and the float stage runs the identical rounding sequence, so the fused
# route is bitwise identical to the unfused XLA reference (the executor
# applies the same final ``jnp.ldexp(out, e_base)``).
#
# The batch-grid variant prepends the batch as the OUTERMOST grid
# dimension — (B, m/bm, n/bn, ell, k/bk) — like the epilogue family; the
# residue stacks arrive as (ell, B, m, k) x (ell, B, n, k).


def _fmod(x, m: int):
    """Floor mod by a positive int32 constant (== jnp.mod bitwise: exact
    integer arithmetic, spelled with lax.rem for Mosaic)."""
    r = jax.lax.rem(x, jnp.int32(m))
    return r + jnp.where(r < 0, jnp.int32(m), jnp.int32(0))


def _crt_epilogue(acc_ref, moduli, qmod, inv, scales):
    """Balanced-Garner digits + ascending-radix f64 sum of the resident
    (ell, bm, bn) int32 residue-product stack."""
    digits = []
    c = None
    for j, mj in enumerate(moduli):
        half = (mj - 1) // 2
        r = _fmod(acc_ref[pl.ds(j, 1)][0], mj)
        acc = r - jnp.where(r > half, jnp.int32(mj), jnp.int32(0))
        for i in range(j):
            acc = acc - digits[i] * jnp.int32(qmod[i][j])
        d = _fmod(acc, mj)
        v = _fmod(d * jnp.int32(inv[j]), mj)
        digits.append(v - jnp.where(v > half, jnp.int32(mj), jnp.int32(0)))
        # mirror ``crt_value``'s FMA-proof term: the scale arrives as a
        # Veltkamp (hi, lo) pair, so both digit products are EXACT f64
        # (7 + 27 bits) and only the running adds round — contracting an
        # exact mul into the add cannot move a bit, keeping the kernel
        # sum bitwise identical to the eager reference.
        hi, lo = scales[j]
        vf = digits[j].astype(jnp.float64)
        t_lo = vf * lo
        c = t_lo if c is None else c + t_lo
        c = c + vf * hi
    return c


def _crt_kernel(moduli, qmod, inv, scales, nk, a_ref, b_ref, o_ref, acc_ref):
    jj = pl.program_id(2)
    kk = pl.program_id(3)
    ell = len(moduli)

    @pl.when((jj == 0) & (kk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[pl.ds(jj, 1)] += jax.lax.dot_general(
        a_ref[0], b_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)[None]

    @pl.when((jj == ell - 1) & (kk == nk - 1))
    def _epilogue():
        o_ref[...] = _crt_epilogue(acc_ref, moduli, qmod, inv, scales)


def _crt_kernel_batched(moduli, qmod, inv, scales, nk, a_ref, b_ref, o_ref,
                        acc_ref):
    jj = pl.program_id(3)
    kk = pl.program_id(4)
    ell = len(moduli)

    @pl.when((jj == 0) & (kk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[pl.ds(jj, 1)] += jax.lax.dot_general(
        a_ref[0, 0], b_ref[0, 0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)[None]

    @pl.when((jj == ell - 1) & (kk == nk - 1))
    def _epilogue():
        o_ref[...] = _crt_epilogue(acc_ref, moduli, qmod, inv, scales)[None]


@kernel_jit(static_argnames=("moduli", "qmod", "inv", "scales", "bm", "bn",
                             "bk", "interpret"))
def int8_matmul_nt_crt(ra: jax.Array, rb: jax.Array, *, moduli, qmod, inv,
                       scales, bm: Optional[int] = None,
                       bn: Optional[int] = None, bk: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Fused residue GEMMs + balanced-Garner CRT reconstruction.

    ra: (ell, m, k) int8 centered residue stack of A_int; rb: (ell, n, k)
    of B_int^T. Returns the (m, n) f64 CRT value PRE-ldexp — the caller
    applies ``jnp.ldexp(out, e_base)``, exactly as after ``crt_value``.
    The Garner constants come from ``core.modular.garner_constants`` as
    hashable static tuples (moduli, Q_i-mod-m_j rows, inverses, f64
    scales). Batch-grid form: (ell, B, m, k) x (ell, B, n, k) residue
    stacks -> (B, m, n).

    Zero-padding is exact end to end: padded k columns contribute zero
    residue products, and all-zero accumulator planes reconstruct to 0.0
    in the padded m/n fringe (sliced off).
    """
    assert ra.dtype == jnp.int8 and rb.dtype == jnp.int8
    assert len(moduli) == ra.shape[0] == rb.shape[0], \
        (len(moduli), ra.shape, rb.shape)
    if ra.ndim == 4:
        return _crt_launch_batched(ra, rb, moduli=moduli, qmod=qmod,
                                   inv=inv, scales=scales, bm=bm, bn=bn,
                                   bk=bk, interpret=interpret)
    ell, m, k = ra.shape
    _, n, k2 = rb.shape
    assert k == k2, (ra.shape, rb.shape)
    bm_, bn_, bk_ = crt_blocks(m, n, k, bm, bn, bk, ell=ell)
    a_p = pad_tail(ra, (bm_, bk_))
    b_p = pad_tail(rb, (bn_, bk_))
    _, mp, kp = a_p.shape
    _, np_, _ = b_p.shape
    gm, gn, gk = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    out = pl.pallas_call(
        functools.partial(_crt_kernel, moduli, qmod, inv, scales, gk),
        grid=(gm, gn, ell, gk),
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda i, j, jj, kk: (jj, i, kk)),
            pl.BlockSpec((1, bn_, bk_), lambda i, j, jj, kk: (jj, j, kk)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, jj, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float64),
        scratch_shapes=[pltpu.VMEM((ell, bm_, bn_), jnp.int32)],
        interpret=interpret,
        name="int8_matmul_nt_crt",
    )(a_p, b_p)
    return out[:m, :n]


def _crt_launch_batched(ra, rb, *, moduli, qmod, inv, scales, bm, bn, bk,
                        interpret):
    """Batch-grid fused-CRT launch: (ell, B, m, k) x (ell, B, n, k)
    residue stacks, batch outermost in the grid."""
    ell, B, m, k = ra.shape
    _, B2, n, k2 = rb.shape
    assert k == k2 and B == B2, (ra.shape, rb.shape)
    bm_, bn_, bk_ = crt_blocks(m, n, k, bm, bn, bk, ell=ell)
    a_p = pad_tail(ra, (bm_, bk_))
    b_p = pad_tail(rb, (bn_, bk_))
    _, _, mp, kp = a_p.shape
    _, _, np_, _ = b_p.shape
    gm, gn, gk = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    out = pl.pallas_call(
        functools.partial(_crt_kernel_batched, moduli, qmod, inv, scales,
                          gk),
        grid=(B, gm, gn, ell, gk),
        in_specs=[
            pl.BlockSpec((1, 1, bm_, bk_),
                         lambda b, i, j, jj, kk: (jj, b, i, kk)),
            pl.BlockSpec((1, 1, bn_, bk_),
                         lambda b, i, j, jj, kk: (jj, b, j, kk)),
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_),
                               lambda b, i, j, jj, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, mp, np_), jnp.float64),
        scratch_shapes=[pltpu.VMEM((ell, bm_, bn_), jnp.int32)],
        interpret=interpret,
        name="int8_matmul_nt_crt",
    )(a_p, b_p)
    return out[:, :m, :n]


# ----------------------------------------------------------------------------
# Streaming-split variants: split + GEMM + scaled accumulation in one
# launch. Operands arrive as (hi, lo) word pairs plus per-row exponents;
# the int8 slices are extracted in VMEM at the head of each k-panel and
# never materialize to HBM.
# ----------------------------------------------------------------------------
#
# Grid is (m/bm, n/bn, k/bk, npairs) with the PAIR dimension innermost —
# the opposite nesting of the epilogue kernels — so each (i, j, kk)
# operand-tile load is split exactly once (at pp == 0) into persistent
# int8 VMEM scratches, then all of the group's pairs consume the resident
# slice planes. The slice chain is prefix-stable, so the scratches hold
# only the prefix the group touches: A needs slices [0, p_lo + npairs),
# B needs [0, t - p_lo + 1). The (kk, pp) walk sums the same int32
# products as the epilogue kernels' (pp, kk) walk — int32 accumulation is
# exact, hence order-independent — and the float epilogue runs the
# identical rounding sequence at the last grid step, so streaming stays
# bitwise identical to every other executor. Padded rows/cols carry
# hi = lo = 0 with exponent 0 and split to all-zero slices, matching the
# zero-padded materialized stacks.
#
# The batch-grid variants prepend the batch as the OUTERMOST grid
# dimension, exactly like the epilogue family.


def _streaming_kernel_sw(w, scale, p_lo, t, npairs, nk, ns_a, ns_b,
                         ahi_ref, alo_ref, aexp_ref, bhi_ref, blo_ref,
                         bexp_ref, c_ref, o_ref, asl_ref, bsl_ref, acc_ref):
    kk = pl.program_id(2)
    pp = pl.program_id(3)

    @pl.when(pp == 0)
    def _split():
        split_tile(asl_ref, ahi_ref[...], alo_ref[...], aexp_ref[...],
                   ns_a, w)
        split_tile(bsl_ref, bhi_ref[...], blo_ref[...], bexp_ref[...],
                   ns_b, w)

    @pl.when((kk == 0) & (pp == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        asl_ref[pl.ds(p_lo + pp, 1)][0], bsl_ref[pl.ds(t - p_lo - pp, 1)][0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when((kk == nk - 1) & (pp == npairs - 1))
    def _epilogue():
        c = c_ref[...]
        o_ref[...] = c + acc_ref[...].astype(c.dtype) * jnp.asarray(
            scale, c.dtype)


def _streaming_kernel_dw(w, scale, p_lo, t, npairs, nk, ns_a, ns_b,
                         ahi_ref, alo_ref, aexp_ref, bhi_ref, blo_ref,
                         bexp_ref, chi_ref, clo_ref, ohi_ref, olo_ref,
                         asl_ref, bsl_ref, acc_ref):
    kk = pl.program_id(2)
    pp = pl.program_id(3)

    @pl.when(pp == 0)
    def _split():
        split_tile(asl_ref, ahi_ref[...], alo_ref[...], aexp_ref[...],
                   ns_a, w)
        split_tile(bsl_ref, bhi_ref[...], blo_ref[...], bexp_ref[...],
                   ns_b, w)

    @pl.when((kk == 0) & (pp == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        asl_ref[pl.ds(p_lo + pp, 1)][0], bsl_ref[pl.ds(t - p_lo - pp, 1)][0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when((kk == nk - 1) & (pp == npairs - 1))
    def _epilogue():
        n_hi, n_lo = dw_accum_step(acc_ref[...], chi_ref[...], clo_ref[...],
                                   scale)
        ohi_ref[...] = n_hi
        olo_ref[...] = n_lo


def _streaming_kernel_batched_sw(w, scale, p_lo, t, npairs, nk, ns_a, ns_b,
                                 ahi_ref, alo_ref, aexp_ref, bhi_ref,
                                 blo_ref, bexp_ref, c_ref, o_ref, asl_ref,
                                 bsl_ref, acc_ref):
    kk = pl.program_id(3)
    pp = pl.program_id(4)

    @pl.when(pp == 0)
    def _split():
        split_tile(asl_ref, ahi_ref[0], alo_ref[0], aexp_ref[0], ns_a, w)
        split_tile(bsl_ref, bhi_ref[0], blo_ref[0], bexp_ref[0], ns_b, w)

    @pl.when((kk == 0) & (pp == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        asl_ref[pl.ds(p_lo + pp, 1)][0], bsl_ref[pl.ds(t - p_lo - pp, 1)][0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when((kk == nk - 1) & (pp == npairs - 1))
    def _epilogue():
        c = c_ref[0]
        o_ref[...] = (c + acc_ref[...].astype(c.dtype) * jnp.asarray(
            scale, c.dtype))[None]


def _streaming_kernel_batched_dw(w, scale, p_lo, t, npairs, nk, ns_a, ns_b,
                                 ahi_ref, alo_ref, aexp_ref, bhi_ref,
                                 blo_ref, bexp_ref, chi_ref, clo_ref,
                                 ohi_ref, olo_ref, asl_ref, bsl_ref,
                                 acc_ref):
    kk = pl.program_id(3)
    pp = pl.program_id(4)

    @pl.when(pp == 0)
    def _split():
        split_tile(asl_ref, ahi_ref[0], alo_ref[0], aexp_ref[0], ns_a, w)
        split_tile(bsl_ref, bhi_ref[0], blo_ref[0], bexp_ref[0], ns_b, w)

    @pl.when((kk == 0) & (pp == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        asl_ref[pl.ds(p_lo + pp, 1)][0], bsl_ref[pl.ds(t - p_lo - pp, 1)][0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when((kk == nk - 1) & (pp == npairs - 1))
    def _epilogue():
        n_hi, n_lo = dw_accum_step(acc_ref[...], chi_ref[0], clo_ref[0],
                                   scale)
        ohi_ref[...] = n_hi[None]
        olo_ref[...] = n_lo[None]


_STREAMING_BATCHED = {_streaming_kernel_sw: _streaming_kernel_batched_sw,
                      _streaming_kernel_dw: _streaming_kernel_batched_dw}


def _streaming_launch(a_ops, b_ops, c_arrays, kernel, *, num_splits, p_lo,
                      t, npairs, w, scale, bm, bn, bk, interpret, name):
    """Shared launch recipe for both streaming variants, 2-D and batched.

    a_ops/b_ops: (hi, lo, exp) operand triples — (m, k)/(m, k)/(m,) for
    the 2-D form, (B, m, k)/(B, m, k)/(B, m) for the batch grid.
    c_arrays: accumulator planes (1 for sw, 2 for dw), carried through
    ``input_output_aliases``.
    """
    ns_a = p_lo + npairs
    ns_b = t - p_lo + 1
    assert 0 <= p_lo and ns_a <= num_splits, (p_lo, npairs, num_splits)
    assert 0 <= t - p_lo - (npairs - 1) and ns_b <= num_splits, \
        (p_lo, t, npairs, num_splits)
    a_hi, a_lo, a_exp = a_ops
    b_hi, b_lo, b_exp = b_ops
    if a_hi.ndim == 3:
        return _streaming_launch_batched(
            a_ops, b_ops, c_arrays, _STREAMING_BATCHED[kernel],
            ns_a=ns_a, ns_b=ns_b, p_lo=p_lo, t=t, npairs=npairs, w=w,
            scale=scale, bm=bm, bn=bn, bk=bk, interpret=interpret,
            name=name)
    m, k = a_hi.shape
    n, k2 = b_hi.shape
    assert k == k2, (a_hi.shape, b_hi.shape)
    bm_, bn_, bk_ = streaming_blocks(m, n, k, bm, bn, bk, num_splits_a=ns_a,
                                     num_splits_b=ns_b,
                                     el_bytes=a_hi.dtype.itemsize)
    a_p = [pad_tail(a_hi, (bm_, bk_)), pad_tail(a_lo, (bm_, bk_)),
           pad_tail(a_exp[..., None], (bm_, 1))]
    b_p = [pad_tail(b_hi, (bn_, bk_)), pad_tail(b_lo, (bn_, bk_)),
           pad_tail(b_exp[..., None], (bn_, 1))]
    c_p = [pad_tail(c, (bm_, bn_)) for c in c_arrays]
    mp, kp = a_p[0].shape
    np_, _ = b_p[0].shape
    gm, gn, gk = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    nc = len(c_p)
    c_spec = pl.BlockSpec((bm_, bn_), lambda i, j, kk, pp: (i, j))
    outs = pl.pallas_call(
        functools.partial(kernel, w, scale, p_lo, t, npairs, gk, ns_a, ns_b),
        grid=(gm, gn, gk, npairs),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk, pp: (i, kk)),
            pl.BlockSpec((bm_, bk_), lambda i, j, kk, pp: (i, kk)),
            pl.BlockSpec((bm_, 1), lambda i, j, kk, pp: (i, jnp.int32(0))),
            pl.BlockSpec((bn_, bk_), lambda i, j, kk, pp: (j, kk)),
            pl.BlockSpec((bn_, bk_), lambda i, j, kk, pp: (j, kk)),
            pl.BlockSpec((bn_, 1), lambda i, j, kk, pp: (j, jnp.int32(0))),
        ] + [c_spec] * nc,
        out_specs=[c_spec] * nc,
        out_shape=[jax.ShapeDtypeStruct((mp, np_), c.dtype) for c in c_p],
        scratch_shapes=[pltpu.VMEM((ns_a, bm_, bk_), jnp.int8),
                        pltpu.VMEM((ns_b, bn_, bk_), jnp.int8),
                        pltpu.VMEM((bm_, bn_), jnp.int32)],
        input_output_aliases={6 + i: i for i in range(nc)},
        interpret=interpret,
        name=name,
    )(*a_p, *b_p, *c_p)
    return [o[:m, :n] for o in outs]


def _streaming_launch_batched(a_ops, b_ops, c_arrays, kernel, *, ns_a, ns_b,
                              p_lo, t, npairs, w, scale, bm, bn, bk,
                              interpret, name):
    """Batch-grid streaming launch: (B, m, k) operand words, (B, m) row
    exponents, (B, m, n) carried accumulators, batch outermost."""
    a_hi, a_lo, a_exp = a_ops
    b_hi, b_lo, b_exp = b_ops
    B, m, k = a_hi.shape
    B2, n, k2 = b_hi.shape
    assert k == k2 and B == B2, (a_hi.shape, b_hi.shape)
    bm_, bn_, bk_ = streaming_blocks(m, n, k, bm, bn, bk, num_splits_a=ns_a,
                                     num_splits_b=ns_b,
                                     el_bytes=a_hi.dtype.itemsize)
    a_p = [pad_tail(a_hi, (bm_, bk_)), pad_tail(a_lo, (bm_, bk_)),
           pad_tail(a_exp[..., None], (bm_, 1))]
    b_p = [pad_tail(b_hi, (bn_, bk_)), pad_tail(b_lo, (bn_, bk_)),
           pad_tail(b_exp[..., None], (bn_, 1))]
    c_p = [pad_tail(c, (bm_, bn_)) for c in c_arrays]
    _, mp, kp = a_p[0].shape
    _, np_, _ = b_p[0].shape
    gm, gn, gk = grid_for((mp, np_, kp), (bm_, bn_, bk_))
    nc = len(c_p)
    c_spec = pl.BlockSpec((1, bm_, bn_), lambda b, i, j, kk, pp: (b, i, j))
    outs = pl.pallas_call(
        functools.partial(kernel, w, scale, p_lo, t, npairs, gk, ns_a, ns_b),
        grid=(B, gm, gn, gk, npairs),
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda b, i, j, kk, pp: (b, i, kk)),
            pl.BlockSpec((1, bm_, bk_), lambda b, i, j, kk, pp: (b, i, kk)),
            pl.BlockSpec((1, bm_, 1),
                         lambda b, i, j, kk, pp: (b, i, jnp.int32(0))),
            pl.BlockSpec((1, bn_, bk_), lambda b, i, j, kk, pp: (b, j, kk)),
            pl.BlockSpec((1, bn_, bk_), lambda b, i, j, kk, pp: (b, j, kk)),
            pl.BlockSpec((1, bn_, 1),
                         lambda b, i, j, kk, pp: (b, j, jnp.int32(0))),
        ] + [c_spec] * nc,
        out_specs=[c_spec] * nc,
        out_shape=[jax.ShapeDtypeStruct((B, mp, np_), c.dtype) for c in c_p],
        scratch_shapes=[pltpu.VMEM((ns_a, bm_, bk_), jnp.int8),
                        pltpu.VMEM((ns_b, bn_, bk_), jnp.int8),
                        pltpu.VMEM((bm_, bn_), jnp.int32)],
        input_output_aliases={6 + i: i for i in range(nc)},
        interpret=interpret,
        name=name,
    )(*a_p, *b_p, *c_p)
    return [o[:, :m, :n] for o in outs]


@kernel_jit(static_argnames=("num_splits", "p_lo", "t", "npairs", "w",
                             "scale", "bm", "bn", "bk", "interpret"))
def int8_matmul_nt_streaming_sw(a_hi: jax.Array, a_lo: jax.Array,
                                a_exp: jax.Array, b_hi: jax.Array,
                                b_lo: jax.Array, b_exp: jax.Array,
                                c: jax.Array, *, num_splits: int, p_lo: int,
                                t: int, npairs: int, w: int, scale: float,
                                bm: Optional[int] = None,
                                bn: Optional[int] = None,
                                bk: Optional[int] = None,
                                interpret: Optional[bool] = None) -> jax.Array:
    """c += (sum_pp A[p_lo+pp] @ B[t-p_lo-pp].T) * scale — with the int8
    slices extracted in VMEM from the (hi, lo, exp) operand words.

    One launch covers one anti-diagonal group, exactly like the epilogue
    variants, but no slice stack exists in HBM: (m, k)/(m,) operand
    arrays in, (m, n) accumulator through. Batch-grid form: (B, m, k)
    words with (B, m) exponents and a (B, m, n) accumulator.
    """
    (out,) = _streaming_launch((a_hi, a_lo, a_exp), (b_hi, b_lo, b_exp),
                               [c], _streaming_kernel_sw,
                               num_splits=num_splits, p_lo=p_lo, t=t,
                               npairs=npairs, w=w, scale=scale, bm=bm,
                               bn=bn, bk=bk, interpret=interpret,
                               name="int8_matmul_nt_streaming_sw")
    return out


@kernel_jit(static_argnames=("num_splits", "p_lo", "t", "npairs", "w",
                             "scale", "bm", "bn", "bk", "interpret"))
def int8_matmul_nt_streaming_dw(a_hi: jax.Array, a_lo: jax.Array,
                                a_exp: jax.Array, b_hi: jax.Array,
                                b_lo: jax.Array, b_exp: jax.Array,
                                c_hi: jax.Array, c_lo: jax.Array, *,
                                num_splits: int, p_lo: int, t: int,
                                npairs: int, w: int, scale: float,
                                bm: Optional[int] = None,
                                bn: Optional[int] = None,
                                bk: Optional[int] = None,
                                interpret: Optional[bool] = None
                                ) -> tuple[jax.Array, jax.Array]:
    """(c_hi, c_lo) += df32(group product) * scale, streaming-split.

    The epilogue runs ``ozaki_accum.dw_accum_step`` — the identical
    rounding sequence of every other executor — so streaming stays
    bitwise identical to the XLA reference. Batch-grid form as in the sw
    variant.
    """
    o_hi, o_lo = _streaming_launch((a_hi, a_lo, a_exp), (b_hi, b_lo, b_exp),
                                   [c_hi, c_lo], _streaming_kernel_dw,
                                   num_splits=num_splits, p_lo=p_lo, t=t,
                                   npairs=npairs, w=w, scale=scale, bm=bm,
                                   bn=bn, bk=bk, interpret=interpret,
                                   name="int8_matmul_nt_streaming_dw")
    return o_hi, o_lo
