"""Pipeline executors — one class per backend/fusion strategy.

``core.ozaki`` is the thin driver: it normalizes operands (transposes B,
folds batches into rows for the "rows"/"grid" layouts), builds a
``PipelinePlan`` (``core.tuning.plan_for``), and calls the executor the
plan selects. Executors own the three pipeline stages:

  * ``split``/``split_dw`` — stage 1, always on a 2-D matrix (the driver
    folds a batch into rows first; splitting is row-independent, so the
    fold is exact).
  * ``gemm``/``products`` — stage 2, the slice GEMMs per anti-diagonal
    group. Operands may be 2-D ``(m, k) x (n, k)`` or 3-D batched
    ``(B, m, k) x (B, n, k)``; the 3-D case runs the explicit batch-grid
    kernel (``int8_matmul_nt_batched``) on the Pallas executors and a
    batch-dimension ``dot_general`` on XLA — never ``vmap``. The pair
    schedule comes from ``plan.diagonals()``, which already reflects the
    plan's fast-mode ``pair_policy``: truncated diagonals mean fewer
    GEMMs here and a shorter pair-grid dimension in the epilogue kernels
    (``npairs`` below) — truncation is threaded into the launch grids,
    never applied as a post-hoc mask.
  * ``accumulate`` — stage 3, the high-precision scaled accumulation,
    ordered smallest terms first; the deferred per-element exponent
    ``e_base`` is applied once at the end (exact power-of-two scaling).
  * ``contract`` — stages 2+3. The epilogue executor overrides this
    whole stage pair: GEMM and accumulation run in one kernel per group
    and the int32 group products never materialize to HBM.

Every executor is bitwise-compatible with ``XlaExecutor`` for both
accumulation modes: integer stages are exact, and the float stages run
identical rounding sequences (enforced by ``tests/test_backend_parity``).

Kernel imports stay lazy (inside methods) to keep ``repro.core``
importable without ``repro.kernels`` and cycle-free.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import tracing

# core.modular imports core.tuning only; its drivers import us lazily,
# so this top-level import is cycle-free.
from .modular import (center_mod, crt_digits, crt_value, garner_constants,
                      residues_from_slices, usable_moduli)
from .splitting import SplitResult, row_exponents, split_int, split_int_dw
from .tuning import BACKENDS, PipelinePlan
from .xmath import DW, dw_add, dw_normalize, ldexp

__all__ = ["BACKENDS", "XlaExecutor", "PallasExecutor", "FusedExecutor",
           "EpilogueExecutor", "StreamingExecutor", "StreamingSplit",
           "ModularXlaExecutor", "ModularPallasExecutor",
           "ModularFusedExecutor", "ModularEpilogueExecutor",
           "get_executor", "gemm_xla", "int32_to_dw"]


def gemm_xla(a8: jax.Array, bt8: jax.Array) -> jax.Array:
    """int8 NT GEMM as one XLA op; 3-D operands contract batched."""
    if a8.ndim == 3:
        return jax.lax.dot_general(
            a8, bt8, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)
    return jax.lax.dot_general(
        a8, bt8, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


def int32_to_dw(p: jax.Array) -> DW:
    """Exact int32 -> df32 conversion (no int64 anywhere: TPU/x32 safe)."""
    low = jnp.bitwise_and(p, jnp.int32(0xFFFF))        # [0, 65535]
    high = p - low                                      # multiple of 2^16
    hi_f = high.astype(jnp.float32)                     # <= 15 sig bits: exact
    lo_f = low.astype(jnp.float32)                      # <= 16 sig bits: exact
    return dw_normalize(hi_f, lo_f)


def _ordered(products):
    return sorted(products, key=lambda tp: -tp[0])      # small terms first


class XlaExecutor:
    """Reference executor: every stage as composite XLA ops."""

    def __init__(self, plan: PipelinePlan):
        self.plan = plan

    # ---- stage 1: split -------------------------------------------------
    @tracing.scoped(tracing.SPLIT)
    def split(self, x: jax.Array, w: int) -> SplitResult:
        return split_int(x, self.plan.num_splits, w)

    @tracing.scoped(tracing.SPLIT)
    def split_dw(self, x: DW, w: int) -> SplitResult:
        return split_int_dw(x, self.plan.num_splits, w)

    # ---- stage 2: slice GEMMs ------------------------------------------
    def gemm(self, a8: jax.Array, bt8: jax.Array) -> jax.Array:
        return gemm_xla(a8, bt8)

    @tracing.scoped(tracing.GEMM)
    def products(self, sa: SplitResult,
                 sb: SplitResult) -> list[tuple[int, jax.Array]]:
        """[(t, P_t int32)] per anti-diagonal group."""
        plan = self.plan
        out = []
        for t, pairs in plan.diagonals():
            # one GEMM per group over the k-concatenated pairs, else one
            # per pair
            tracing.gemm_launch(len(pairs),
                                launches=1 if plan.concat_k else len(pairs))
            if plan.concat_k:
                a_cat = jnp.concatenate([sa.slices[p] for p, _ in pairs],
                                        axis=-1)
                b_cat = jnp.concatenate([sb.slices[q] for _, q in pairs],
                                        axis=-1)
                out.append((t, self.gemm(a_cat, b_cat)))
            elif plan.fuse_diagonals:
                p_t = self.gemm(sa.slices[pairs[0][0]], sb.slices[pairs[0][1]])
                for p, q in pairs[1:]:
                    p_t = p_t + self.gemm(sa.slices[p], sb.slices[q])
                out.append((t, p_t))
            else:
                # paper-faithful: pair products stay separate
                out.extend((t, self.gemm(sa.slices[p], sb.slices[q]))
                           for p, q in pairs)
        return out

    # ---- stage 3: high-precision scaled accumulation -------------------
    @tracing.scoped(tracing.GEMM)
    def accumulate(self, products, e_base: jax.Array, w: int, shape):
        if self.plan.accum == "f64":
            c = jnp.zeros(shape, jnp.float64)
            for t, p_t in _ordered(products):
                c = c + ldexp(p_t.astype(jnp.float64),
                              e_base - (t + 2) * w)
            return c
        acc = DW(jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
        for t, p_t in _ordered(products):
            scale = jnp.float32(2.0 ** (-(t + 2) * w))  # exact power of two
            term = int32_to_dw(p_t)
            acc = dw_add(acc, DW(term.hi * scale, term.lo * scale))
        with tracing.scope(tracing.SCALE_OUT):
            return DW(ldexp(acc.hi, e_base), ldexp(acc.lo, e_base))

    # ---- stages 2+3 -----------------------------------------------------
    def contract(self, sa: SplitResult, sb: SplitResult, w: int,
                 e_base: jax.Array, shape):
        return self.accumulate(self.products(sa, sb), e_base, w, shape)


class PallasExecutor(XlaExecutor):
    """Slice GEMMs on the Pallas MXU kernels; split/accumulate stay XLA.

    3-D operands run the explicit batch-grid GEMM (the batch is the
    outermost grid dimension of ONE kernel launch — no vmap wrapper).
    """

    def gemm(self, a8: jax.Array, bt8: jax.Array) -> jax.Array:
        from repro.kernels import int8_matmul_nt, int8_matmul_nt_batched
        tile = self.plan.tile
        kw = dict(bm=tile.bm, bn=tile.bn, bk=tile.bk,
                  interpret=self.plan.interpret)
        if a8.ndim == 3:
            return int8_matmul_nt_batched(a8, bt8, **kw)
        return int8_matmul_nt(a8, bt8, **kw)


class FusedExecutor(PallasExecutor):
    """The PR 1 ``pallas_fused`` pipeline (``fusion="stages"``): one-pass
    SplitInt kernel, Pallas GEMMs, fused scaled-accumulation kernels.
    Batched accumulation folds ``(B, m, n)`` onto ``(B*m, n)`` — the
    kernels are elementwise, so the fold is exact.
    """

    @tracing.scoped(tracing.SPLIT)
    def split(self, x: jax.Array, w: int) -> SplitResult:
        from repro.kernels import fused_split_dw
        exp = row_exponents(x)
        tile = self.plan.tile
        slices = fused_split_dw(x, jnp.zeros_like(x), exp,
                                num_splits=self.plan.num_splits, w=w,
                                bm=tile.split_bm, bk=tile.split_bk,
                                interpret=self.plan.interpret)
        return SplitResult(slices, exp, w)

    @tracing.scoped(tracing.SPLIT)
    def split_dw(self, x: DW, w: int) -> SplitResult:
        from repro.kernels import fused_split_dw
        exp = row_exponents(x.hi)
        tile = self.plan.tile
        slices = fused_split_dw(x.hi, x.lo, exp,
                                num_splits=self.plan.num_splits, w=w,
                                bm=tile.split_bm, bk=tile.split_bk,
                                interpret=self.plan.interpret)
        return SplitResult(slices, exp, w)

    @tracing.scoped(tracing.GEMM)
    def accumulate(self, products, e_base: jax.Array, w: int, shape):
        from repro.kernels import accum_scaled_dw, accum_scaled_sw
        tile = self.plan.tile
        kw = dict(bm=tile.accum_bm, bn=tile.accum_bn,
                  interpret=self.plan.interpret)
        fold = len(shape) > 2
        flat = (-1, shape[-1])

        def fold2d(x):
            return x.reshape(flat) if fold else x

        if self.plan.accum == "f64":
            c = fold2d(jnp.zeros(shape, jnp.float64))
            for t, p_t in _ordered(products):
                c = accum_scaled_sw(fold2d(p_t), c,
                                    scale=2.0 ** (-(t + 2) * w), **kw)
            with tracing.scope(tracing.SCALE_OUT):
                return ldexp(c.reshape(shape), e_base)
        c_hi = fold2d(jnp.zeros(shape, jnp.float32))
        c_lo = fold2d(jnp.zeros(shape, jnp.float32))
        for t, p_t in _ordered(products):
            c_hi, c_lo = accum_scaled_dw(fold2d(p_t), c_hi, c_lo,
                                         scale=2.0 ** (-(t + 2) * w), **kw)
        with tracing.scope(tracing.SCALE_OUT):
            return DW(ldexp(c_hi.reshape(shape), e_base),
                      ldexp(c_lo.reshape(shape), e_base))


class EpilogueExecutor(FusedExecutor):
    """``fusion="epilogue"``: GEMM + scaled accumulation in one kernel.

    One launch per anti-diagonal group; the group's int32 product lives
    only in a VMEM scratch block (``tuning.hbm_pass_model`` drops the
    per-group P read). ``concat_k`` needs no concatenated operands here —
    the pair grid dimension accumulates the same exact int32 sum. A 3-D
    output shape runs the batch-grid epilogue kernels ((s, B, m, k)
    slice stacks, batch outermost in the grid): stacked-weights batches
    keep epilogue fusion instead of downgrading to the stage-fused
    pipeline.
    """

    def _groups(self):
        """(t, p_lo, npairs) in accumulation order: t descending, and for
        the unfused schedule pairs in ``diagonals()`` order (matching the
        stable ``_ordered`` sort of the reference products list).
        ``npairs`` reflects the plan's ``pair_policy``: a truncated
        diagonal launches a shorter pair-grid dimension (the kept pairs
        are the prefix from ``p_lo``, which the kernels' affine slice
        indexing covers unchanged)."""
        plan = self.plan
        groups = []
        for t, pairs in plan.diagonals():
            if plan.fuse_diagonals or plan.concat_k:
                groups.append((t, pairs[0][0], len(pairs)))
            else:
                groups.extend((t, p, 1) for p, _ in pairs)
        return sorted(groups, key=lambda g: -g[0])

    @tracing.scoped(tracing.GEMM)
    def contract(self, sa: SplitResult, sb: SplitResult, w: int,
                 e_base: jax.Array, shape):
        from repro.kernels import (int8_matmul_nt_epilogue_dw,
                                   int8_matmul_nt_epilogue_sw)
        assert len(shape) in (2, 3), shape    # 3-D: batch-grid kernels
        tile = self.plan.tile
        kw = dict(bm=tile.bm, bn=tile.bn, bk=tile.bk,
                  interpret=self.plan.interpret)
        if self.plan.accum == "f64":
            c = jnp.zeros(shape, jnp.float64)
            for t, p_lo, npairs in self._groups():
                tracing.gemm_launch(npairs)
                c = int8_matmul_nt_epilogue_sw(
                    sa.slices, sb.slices, c, p_lo=p_lo, t=t, npairs=npairs,
                    scale=2.0 ** (-(t + 2) * w), **kw)
            with tracing.scope(tracing.SCALE_OUT):
                return ldexp(c, e_base)
        c_hi = jnp.zeros(shape, jnp.float32)
        c_lo = jnp.zeros(shape, jnp.float32)
        for t, p_lo, npairs in self._groups():
            tracing.gemm_launch(npairs)
            c_hi, c_lo = int8_matmul_nt_epilogue_dw(
                sa.slices, sb.slices, c_hi, c_lo, p_lo=p_lo, t=t,
                npairs=npairs, scale=2.0 ** (-(t + 2) * w), **kw)
        with tracing.scope(tracing.SCALE_OUT):
            return DW(ldexp(c_hi, e_base), ldexp(c_lo, e_base))


class StreamingSplit(NamedTuple):
    """Stage-1 "result" of the streaming pipeline: nothing is split yet.

    ``split`` only computes the per-row exponents; the (hi, lo) operand
    words ride forward so the streaming GEMM kernels can extract the int8
    slices tile-wise in VMEM — the slice stacks never exist in HBM.
    Duck-types the ``SplitResult`` fields the driver reads (exp, w).
    """

    hi: jax.Array
    lo: jax.Array
    exp: jax.Array
    w: int


class StreamingExecutor(EpilogueExecutor):
    """``fusion="streaming"``: split + GEMM + accumulation in one kernel.

    The anti-diagonal group schedule, rounding sequences and accumulation
    order are exactly the epilogue executor's; the difference is purely
    where the slices live. ``split``/``split_dw`` are no-ops that carry
    the operand words plus precomputed row exponents forward (the
    exponents are full-row reductions, so they must be computed before
    tiling), and each group's kernel extracts the slice prefix it needs
    into VMEM scratch. Extraction is elementwise per (row, col) given the
    row exponent, so the tile-wise in-kernel split is bitwise identical
    to the materialized stacks — the parity matrix enforces it.
    """

    @tracing.scoped(tracing.SPLIT)
    def split(self, x: jax.Array, w: int) -> StreamingSplit:
        return StreamingSplit(x, jnp.zeros_like(x), row_exponents(x), w)

    @tracing.scoped(tracing.SPLIT)
    def split_dw(self, x: DW, w: int) -> StreamingSplit:
        return StreamingSplit(x.hi, x.lo, row_exponents(x.hi), w)

    @tracing.scoped(tracing.GEMM)
    def contract(self, sa: StreamingSplit, sb: StreamingSplit, w: int,
                 e_base: jax.Array, shape):
        from repro.kernels import (int8_matmul_nt_streaming_dw,
                                   int8_matmul_nt_streaming_sw)
        assert len(shape) in (2, 3), shape    # 3-D: batch-grid kernels
        plan = self.plan
        tile = plan.tile
        kw = dict(num_splits=plan.num_splits, w=w, bm=tile.bm, bn=tile.bn,
                  bk=tile.bk, interpret=plan.interpret)
        a_ops = (sa.hi, sa.lo, sa.exp)
        b_ops = (sb.hi, sb.lo, sb.exp)
        if plan.accum == "f64":
            c = jnp.zeros(shape, jnp.float64)
            for t, p_lo, npairs in self._groups():
                tracing.gemm_launch(npairs)
                c = int8_matmul_nt_streaming_sw(
                    *a_ops, *b_ops, c, p_lo=p_lo, t=t, npairs=npairs,
                    scale=2.0 ** (-(t + 2) * w), **kw)
            with tracing.scope(tracing.SCALE_OUT):
                return ldexp(c, e_base)
        c_hi = jnp.zeros(shape, jnp.float32)
        c_lo = jnp.zeros(shape, jnp.float32)
        for t, p_lo, npairs in self._groups():
            tracing.gemm_launch(npairs)
            c_hi, c_lo = int8_matmul_nt_streaming_dw(
                *a_ops, *b_ops, c_hi, c_lo, p_lo=p_lo, t=t, npairs=npairs,
                scale=2.0 ** (-(t + 2) * w), **kw)
        with tracing.scope(tracing.SCALE_OUT):
            return DW(ldexp(c_hi, e_base), ldexp(c_lo, e_base))


class ModularXlaExecutor:
    """Ozaki Scheme II reference executor (``plan.scheme="ozaki2_fp64"``).

    Stage 1 reuses ``split_int`` — the ``num_splits`` slices ARE the
    integerization (``A_int = sum_p slices[p] * 2^{(s-1-p)w}``, beta =
    s*w bits kept). Stage 2 maps the slices to centered int8 residues
    per modulus and runs ONE int8 NT GEMM per modulus, with the modulus
    axis as the leading batch dimension (a batched operand folds the
    (modulus, batch) product onto that same axis — still one launch).
    Stage 3 is the exact CRT reconstruction (``core.modular``): Garner
    digits in int32, FP64 sum smallest radix first, deferred ``e_base``
    applied once at the end — the same rounding-sequence discipline the
    Scheme I executors keep, so the guaranteed bound
    (``modular.modular_error_bound``) is the whole error story.

    The moduli re-derive from the plan deterministically:
    ``usable_moduli(k)[:plan.num_moduli]`` — selection always takes a
    prefix of the usable pool, so the plan's count is the full identity.
    """

    def __init__(self, plan: PipelinePlan):
        self.plan = plan

    # ---- stage 1: integerize (slice-built) -----------------------------
    def split(self, x: jax.Array, w: int) -> SplitResult:
        return split_int(x, self.plan.num_splits, w)

    # ---- stage 2: residue GEMMs ----------------------------------------
    def gemm(self, a8: jax.Array, bt8: jax.Array) -> jax.Array:
        return gemm_xla(a8, bt8)

    # ---- stages 2+3 -----------------------------------------------------
    def contract(self, sa: SplitResult, sb: SplitResult, w: int,
                 e_base: jax.Array, shape):
        k = sa.slices.shape[-1]
        moduli = usable_moduli(k)[:self.plan.num_moduli]
        ra = residues_from_slices(sa.slices, w, moduli)
        rb = residues_from_slices(sb.slices, w, moduli)
        if ra.ndim == 4:                 # batched: (ell, B, rows, k)
            ell, bsz = ra.shape[0], ra.shape[1]
            p = self.gemm(ra.reshape(ell * bsz, ra.shape[2], k),
                          rb.reshape(ell * bsz, rb.shape[2], k))
            p = p.reshape((ell,) + shape)
        else:                            # 2-D: modulus axis is the batch
            p = self.gemm(ra, rb)
        digits = crt_digits(center_mod(p, moduli), moduli)
        return crt_value(digits, moduli, self.plan.beta, e_base)


class ModularPallasExecutor(ModularXlaExecutor):
    """Residue GEMMs on the batch-grid Pallas MXU kernel: the modulus
    (or modulus x batch) axis is the outermost grid dimension of ONE
    ``int8_matmul_nt_batched`` launch — the operands are always 3-D
    here, so the batched kernel is the only entry needed."""

    def gemm(self, a8: jax.Array, bt8: jax.Array) -> jax.Array:
        from repro.kernels import int8_matmul_nt_batched
        tile = self.plan.tile
        return int8_matmul_nt_batched(a8, bt8, bm=tile.bm, bn=tile.bn,
                                      bk=tile.bk,
                                      interpret=self.plan.interpret)


class ModularFusedExecutor(ModularPallasExecutor):
    """``pallas_fused`` Scheme II: integerize with the one-pass SplitInt
    kernel (stage-1 fusion — the residue GEMM stage is already a single
    batched launch, and CRT is elementwise XLA)."""

    def split(self, x: jax.Array, w: int) -> SplitResult:
        return FusedExecutor.split(self, x, w)


class ModularEpilogueExecutor(ModularFusedExecutor):
    """``fusion="epilogue"`` Scheme II: residue GEMMs + balanced-Garner
    CRT reconstruction in ONE kernel launch.

    The per-modulus int32 product planes accumulate in a (ell, bm, bn)
    VMEM scratch stack over the (modulus, k) grid walk and the CRT
    epilogue reconstructs the f64 value at the last grid step — they
    never round-trip through HBM (``tuning.hbm_pass_model`` drops the
    2*ell accumulation passes). The kernel replays
    ``crt_digits``/``crt_value``'s exact integer recurrence and f64
    rounding sequence with host-baked Garner constants
    (``modular.garner_constants``), so the fused route stays bitwise
    identical to the unfused XLA reference.
    """

    def contract(self, sa: SplitResult, sb: SplitResult, w: int,
                 e_base: jax.Array, shape):
        from repro.kernels import int8_matmul_nt_crt
        k = sa.slices.shape[-1]
        moduli = usable_moduli(k)[:self.plan.num_moduli]
        ra = residues_from_slices(sa.slices, w, moduli)
        rb = residues_from_slices(sb.slices, w, moduli)
        mods, qmod, inv, scales = garner_constants(moduli, self.plan.beta)
        tile = self.plan.tile
        out = int8_matmul_nt_crt(ra, rb, moduli=mods, qmod=qmod, inv=inv,
                                 scales=scales, bm=tile.bm, bn=tile.bn,
                                 bk=tile.bk, interpret=self.plan.interpret)
        return ldexp(out, e_base)


def get_executor(plan: PipelinePlan) -> XlaExecutor:
    if getattr(plan, "scheme", "ozaki_fp64") == "ozaki2_fp64":
        if plan.backend == "xla":
            return ModularXlaExecutor(plan)
        if plan.backend == "pallas":
            return ModularPallasExecutor(plan)
        if plan.backend == "pallas_fused":
            if plan.fusion == "epilogue":
                return ModularEpilogueExecutor(plan)
            return ModularFusedExecutor(plan)
        raise ValueError(f"unknown backend {plan.backend!r}; "
                         f"expected one of {BACKENDS}")
    if plan.backend == "xla":
        return XlaExecutor(plan)
    if plan.backend == "pallas":
        return PallasExecutor(plan)
    if plan.backend == "pallas_fused":
        if plan.fusion == "streaming":
            return StreamingExecutor(plan)
        if plan.fusion == "epilogue":
            return EpilogueExecutor(plan)
        return FusedExecutor(plan)
    raise ValueError(f"unknown backend {plan.backend!r}; "
                     f"expected one of {BACKENDS}")
