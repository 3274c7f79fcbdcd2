"""Shared launch-configuration layer for the Ozaki Pallas kernels.

All three kernels (``int8_gemm``, ``ozaki_split``, ``ozaki_accum``) follow
the same launch recipe: shrink the requested block to the (aligned) array
extent, zero-pad the operands up to a whole number of blocks, launch a
dense grid, and slice the padding back off. This module centralizes that
recipe so the kernels agree on alignment rules; the tuning layer
(``repro.core.tuning``) selects the block shapes themselves
(``TilePlan``) that flow into these helpers.

TPU tiling constraints (see the Pallas guide): the last dimension of a
block should be a multiple of 128 lanes; the second-to-last a multiple of
the dtype's sublane count (8 for f32, 32 for int8). In interpret mode any
shape works, but keeping the compiled-mode constraints here means the same
launch parameters lower to Mosaic unchanged.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

LANE = 128          # last-dim tile multiple (all dtypes)
SUBLANE_F32 = 8     # second-to-last multiple, 4-byte dtypes
SUBLANE_I8 = 32     # second-to-last multiple, 1-byte dtypes

VMEM_BYTES = 16 * 2 ** 20   # default scoped VMEM of a Mosaic kernel
SPLIT_LIVE_PLANES = 8       # float temporaries of the in-kernel split

# The GEMM family's blocks when the plan requests none: MXU-aligned
# 256 x 256 x 512 (int8_matmul_nt, the streaming and fused-CRT kernels).
GEMM_BLOCKS = (256, 256, 512)
# The scoped VMEM an epilogue launch declares when its modelled working
# set passes ``VMEM_BYTES``: a v5e core holds 128 MiB, and what the model
# leaves below this limit is margin for Mosaic's own scratch.
EPILOGUE_VMEM_LIMIT = 96 * 2 ** 20
# (bm, bn) temporaries the model allows the epilogue body. Compiled for a
# v5e, Mosaic needed 6.25 to 7 such f32 planes beyond the pipeline
# buffers with the body unrolled over the block, and under one since the
# body runs in pieces (``int8_gemm.EPILOGUE_K_CHUNK``, ``_ROW_STRIP``).
# The rest is margin, and it holds the output tile at 1024 x 1024, the
# largest the chip was measured at.
EPILOGUE_LIVE_PLANES = 8


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means the host default, ``kernels.ops.INTERPRET``: Pallas
    interpret mode off a TPU, Mosaic lowering on one."""
    if interpret is not None:
        return interpret
    from . import ops
    return ops.INTERPRET


def kernel_jit(*, static_argnames: tuple[str, ...]):
    """``jax.jit`` for a kernel wrapper with an ``interpret=None`` keyword.

    The default is resolved before the call reaches the jit cache, so a
    trace never bakes in a stale host default.
    """
    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=static_argnames)

        @functools.wraps(fn)
        def call(*args, interpret: Optional[bool] = None, **kwargs):
            return jitted(*args, interpret=resolve_interpret(interpret),
                          **kwargs)
        return call
    return wrap


def align_up(x: int, align: int) -> int:
    """Smallest multiple of ``align`` >= x."""
    return -(-x // align) * align


def shrink_block(requested: int, extent: int, align: int) -> int:
    """Block actually launched: the request, capped at the aligned extent.

    Tiny inputs get a single just-big-enough block instead of a padded
    256-wide one (interpret-mode tests sweep shapes down to 7).
    """
    return min(requested, align_up(extent, align))


def pad_tail(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    """Zero-pad the trailing ``len(mults)`` dims up to whole blocks."""
    nd = len(mults)
    pads = [(0, 0)] * (x.ndim - nd) + [
        (0, (-d) % m) for d, m in zip(x.shape[-nd:], mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def grid_for(shape: tuple[int, ...], blocks: tuple[int, ...]) -> tuple[int, ...]:
    """Dense grid over padded ``shape`` (must divide exactly)."""
    assert all(d % b == 0 for d, b in zip(shape, blocks)), (shape, blocks)
    return tuple(d // b for d, b in zip(shape, blocks))


# ----------------------------------------------------------------------------
# Named block recipes — one per kernel family, so every launch site agrees
# on the alignment rules (the planner in ``repro.core.tuning`` mirrors them).
# ----------------------------------------------------------------------------

def gemm_blocks(m: int, n: int, k: int, bm: Optional[int] = None,
                bn: Optional[int] = None,
                bk: Optional[int] = None) -> tuple[int, int, int]:
    """Blocks for the int8 NT GEMM family: (m, k) x (n, k) -> (m, n).

    A ``None`` block is no request: it takes ``GEMM_BLOCKS``. bm/bn are
    sublane dims of int8 operand tiles; bn doubles as the lane dim of
    the int32 (or float) output tile, so the stricter 128 alignment
    applies to it and to bk.
    """
    bm, bn, bk = (d if r is None else r
                  for r, d in zip((bm, bn, bk), GEMM_BLOCKS))
    return (shrink_block(bm, m, SUBLANE_I8), shrink_block(bn, n, LANE),
            shrink_block(bk, k, LANE))


def epilogue_working_set(bm: int, bn: int, bk: int, *, planes: int,
                         el_bytes: int) -> int:
    """VMEM bytes of one epilogue-GEMM launch at blocks (bm, bn, bk).

    The int8 A and B tiles and the ``planes`` carried accumulator planes,
    in and out, all double-buffered by the Pallas pipeline; the (bm, bn)
    int32 product scratch; and ``EPILOGUE_LIVE_PLANES`` (bm, bn)
    temporaries of the epilogue body.
    """
    operands = 2 * bk * (bm + bn)
    carried = planes * 2 * 2 * el_bytes * bm * bn
    scratch = 4 * bm * bn
    temps = EPILOGUE_LIVE_PLANES * el_bytes * bm * bn
    return operands + carried + scratch + temps


def _halve(block: int, align: int) -> int:
    """The next power of two below ``block`` (an extent-shrunk block need
    not be one), floored at ``align``."""
    return max(align, 1 << ((block - 1).bit_length() - 1))


def epilogue_blocks(m: int, n: int, k: int, bm: Optional[int] = None,
                    bn: Optional[int] = None, bk: Optional[int] = None, *,
                    planes: int, el_bytes: int) -> tuple[int, int, int]:
    """Blocks for the epilogue-fused GEMM: the largest output tile, then
    the largest reduction slab, whose ``epilogue_working_set`` fits
    ``EPILOGUE_VMEM_LIMIT``.

    Each output tile streams the whole K strip of both slice stacks, so
    A is read n/bn times and B m/bm times: the output tile sets the
    operand traffic and, with bk, the number of grid steps. A ``None``
    block is no request; a number is a request the recipe only shrinks.
    Blocks start at the request or the aligned extent, and halve to
    powers of two (the larger of bm and bn first, then bk) until the
    working set fits, so on power-of-two extents they divide them and
    ``pad_tail`` copies nothing.
    """
    bm_, bn_, bk_ = (align_up(d, a) if r is None else shrink_block(r, d, a)
                     for r, d, a in ((bm, m, SUBLANE_I8), (bn, n, LANE),
                                     (bk, k, LANE)))

    def fits(bm, bn, bk):
        return epilogue_working_set(bm, bn, bk, planes=planes,
                                    el_bytes=el_bytes) <= EPILOGUE_VMEM_LIMIT

    while not fits(bm_, bn_, min(bk_, LANE)):
        if bm_ >= bn_ and bm_ > SUBLANE_I8:
            bm_ = _halve(bm_, SUBLANE_I8)
        elif bn_ > LANE:
            bn_ = _halve(bn_, LANE)
        elif bm_ > SUBLANE_I8:
            bm_ = _halve(bm_, SUBLANE_I8)
        else:
            break
    while not fits(bm_, bn_, bk_) and bk_ > LANE:
        bk_ = _halve(bk_, LANE)
    return bm_, bn_, bk_


def epilogue_vmem_limit(bm: int, bn: int, bk: int, *, planes: int,
                        el_bytes: int) -> Optional[int]:
    """The scoped VMEM an epilogue launch at these blocks declares:
    ``None`` (Mosaic's default) while the working set fits
    ``VMEM_BYTES``, else ``EPILOGUE_VMEM_LIMIT``, the limit
    ``epilogue_blocks`` fits it to."""
    ws = epilogue_working_set(bm, bn, bk, planes=planes, el_bytes=el_bytes)
    return None if ws <= VMEM_BYTES else EPILOGUE_VMEM_LIMIT


def int8_tile_blocks(m: int, k: int, bm: int, bk: int) -> tuple[int, int]:
    """Blocks for kernels tiled over an (m, k) int8-output matrix (split)."""
    return shrink_block(bm, m, SUBLANE_I8), shrink_block(bk, k, LANE)


def elementwise_blocks(m: int, n: int, bm: int, bn: int) -> tuple[int, int]:
    """Blocks for elementwise (m, n) kernels over 4-byte dtypes (accum)."""
    return shrink_block(bm, m, SUBLANE_F32), shrink_block(bn, n, LANE)


def _streaming_working_set(bm: int, bn: int, bk: int, *, num_splits_a: int,
                           num_splits_b: int, el_bytes: int) -> int:
    """VMEM bytes resident per streaming-GEMM grid step.

    Operand tiles arrive as (hi, lo) word pairs plus per-row exponent
    columns (an (r, 1) int32 block pads to 128 lanes); the in-kernel
    split lands ``num_splits_a`` / ``num_splits_b`` int8 slice planes in
    persistent scratch next to the int32 product accumulator and up to
    two carried float accumulator planes, and keeps about
    ``SPLIT_LIVE_PLANES`` float planes of the larger operand tile live
    while it runs — without that term a 4096^3, s=9 launch picks
    bk=512 and overflows the 16 MiB scoped VMEM.
    """
    operands = 2 * el_bytes * (bm * bk + bn * bk) + 4 * LANE * (bm + bn)
    slices = num_splits_a * bm * bk + num_splits_b * bn * bk
    accum = 4 * bm * bn + 2 * 2 * el_bytes * bm * bn   # int32 + in/out C
    split = SPLIT_LIVE_PLANES * el_bytes * max(bm, bn) * bk
    return operands + slices + accum + split


def _crt_working_set(bm: int, bn: int, bk: int, *, ell: int) -> int:
    """VMEM bytes resident per fused-CRT GEMM grid step.

    int8 operand tiles plus the persistent (ell, bm, bn) int32 residue
    accumulator stack — the whole modulus axis must stay resident for the
    Garner epilogue — and the f64 output tile the epilogue writes.
    """
    operands = bm * bk + bn * bk
    accum = 4 * ell * bm * bn
    out = 8 * bm * bn
    return operands + accum + out


def crt_blocks(m: int, n: int, k: int, bm: Optional[int],
               bn: Optional[int], bk: Optional[int], *,
               ell: int, vmem_budget: int = VMEM_BYTES // 2
               ) -> tuple[int, int, int]:
    """Blocks for the fused-CRT residue GEMM: validated against the VMEM
    budget including the (ell, bm, bn) int32 accumulator stack.

    Starts from the standard GEMM shrink, then halves bm -> bn -> bk (to
    their alignment floors) until the working set fits — the accumulator
    stack scales with bm*bn, so the output tile shrinks first. Raises
    ``ValueError`` if even the floor tile exceeds the budget: the CRT
    epilogue needs every modulus plane resident, so there is no smaller
    launch.
    """
    bm_, bn_, bk_ = gemm_blocks(m, n, k, bm, bn, bk)
    ws = functools.partial(_crt_working_set, ell=ell)
    while ws(bm_, bn_, bk_) > vmem_budget:
        if bm_ > SUBLANE_I8:
            bm_ //= 2
        elif bn_ > LANE:
            bn_ //= 2
        elif bk_ > LANE:
            bk_ //= 2
        else:
            raise ValueError(
                "fused-CRT epilogue cannot fit VMEM: floor tile "
                f"({bm_}, {bn_}, {bk_}) with {ell} modulus planes needs "
                f"{ws(bm_, bn_, bk_)} bytes > budget {vmem_budget}")
    return bm_, bn_, bk_


def streaming_blocks(m: int, n: int, k: int, bm: Optional[int],
                     bn: Optional[int], bk: Optional[int], *,
                     num_splits_a: int, num_splits_b: int, el_bytes: int,
                     vmem_budget: int = VMEM_BYTES // 2
                     ) -> tuple[int, int, int]:
    """Blocks for the streaming-split GEMM: validated against the VMEM
    budget including the (s, bm, bk) / (s, bn, bk) slice scratches.

    Starts from the standard GEMM shrink, then halves bk -> bm -> bn (to
    their alignment floors) until the streaming working set fits. Raises
    ``ValueError`` if even the floor tile exceeds the budget — streaming
    needs the whole slice chain resident, so there is no smaller launch.
    """
    bm_, bn_, bk_ = gemm_blocks(m, n, k, bm, bn, bk)
    ws = functools.partial(_streaming_working_set, num_splits_a=num_splits_a,
                           num_splits_b=num_splits_b, el_bytes=el_bytes)
    while ws(bm_, bn_, bk_) > vmem_budget:
        if bk_ > LANE:
            bk_ //= 2
        elif bm_ > SUBLANE_I8:
            bm_ //= 2
        elif bn_ > LANE:
            bn_ //= 2
        else:
            raise ValueError(
                "streaming split cannot fit VMEM: floor tile "
                f"({bm_}, {bn_}, {bk_}) with {num_splits_a}+{num_splits_b} "
                f"slice planes needs {ws(bm_, bn_, bk_)} bytes "
                f"> budget {vmem_budget}")
    return bm_, bn_, bk_
