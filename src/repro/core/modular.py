"""Ozaki Scheme II — modular-arithmetic GEMM emulation (arXiv:2504.08009).

Scheme I (``core.ozaki``) splits each operand into ``s`` int8 mantissa
slices and pays up to ``s(s+1)/2`` slice-pair int8 GEMMs. Scheme II
rounds each operand to ``beta`` mantissa bits relative to its row
exponent — so the scaled operands are *integers* bounded by ``2^beta`` —
and computes the exact integer product ``C_int = A_int @ B_int^T`` in a
**residue number system**: one int8 GEMM per modulus, ``ell`` moduli
total, with ``ell`` growing *linearly* in the mantissa budget where
Scheme I's pair count grows quadratically.

The pipeline (every integer stage exact by construction):

1. **Integerize** — reuse ``splitting.split_int``: ``s`` slices of ``w``
   bits each represent ``A_int = sum_p slices[p] * 2^{(s-1-p)w}``
   exactly, with ``A_kept = 2^{ea - beta} * A_int`` and ``beta = s*w``.
   Truncation toward zero gives ``|A - A_kept| <= 2^{ea - beta}``.
2. **Residues** (``residues_from_slices``) — per modulus ``m_j`` (odd
   primes <= 251), the centered residue ``A_int mod m_j`` is computed
   from the slices with host-precomputed weights ``2^{(s-1-p)w} mod m_j``
   — an int32 tensordot (max partial ``s * 127 * 250 < 2^21``) followed
   by one mod: never a float remainder, so exactness is structural.
   Centered residues lie in ``[-(m_j-1)/2, (m_j-1)/2] ⊆ [-125, 125]``:
   int8 operands for the MXU.
3. **Residue GEMMs** — ``ell`` int8 NT GEMMs with int32 accumulation,
   batched along the modulus axis (the existing batch-grid Pallas kernel
   ``kernels.int8_matmul_nt_batched`` runs all ``ell`` in ONE launch).
   ``usable_moduli`` guarantees ``k * ((m-1)/2)^2 <= 2^31 - 1``: no
   accumulator overflow for any modulus kept.
4. **CRT reconstruction** (``crt_digits`` / ``crt_value``) — Garner's
   mixed-radix algorithm with *balanced* digits: odd moduli make the
   balanced representation unique over ``(-M/2, M/2)``, and
   ``select_moduli`` guarantees ``M > 2k * 4^beta > 2 |C_int|``, so the
   digits reconstruct ``C_int`` exactly (an O(ell^2) elementwise int32
   pass — every intermediate bounded well below 2^31). The FP64 result
   is ``ldexp(sum_j v_j * float(Q_j) * 2^{-2 beta}, ea_i + eb_j)``,
   accumulated smallest radix first.

The guaranteed error bound mirrors ``core.accuracy.error_bound``:
``k * modular_eta(beta)`` covers the operand truncation and
``modular_accum_floor`` covers the float reconstruction rounding —
``modular_error_bound`` is the sum, on the same ``2^{ea_i + eb_j}``
normalization ``accuracy.scaled_error`` measures.

Cost crossover (the reason this module exists): meeting Scheme I's
``s``-split accuracy needs ``beta ~ s*w`` bits, i.e. ``ell ~
(2 s w + log2 k) / 8`` moduli, versus ``s(s+1)/2`` slice pairs — at
``s = 7, k = 4096`` that is 15 residue GEMMs against 28 pair GEMMs, and
the gap widens with ``s``. ``core.accuracy.resolve_accuracy`` arbitrates
per ``(shape, target)`` using exactly these counts.

This module is import-cycle-free with the executor layer:
``core.executors`` imports it at module top (for the ``ModularExecutor``
family); the drivers here import ``get_executor`` lazily.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .analytic import DGEMM_MANTISSA_SPACE
from .splitting import SplitResult, split_int
from .tuning import PipelinePlan, TilePlan
from .xmath import ldexp, refuse_f64_on_tpu

__all__ = ["MAX_BETA", "ModularConfig", "ModularPoint", "center_mod",
           "crt_digits", "crt_value", "crt_value_dw", "garner_constants",
           "min_beta_for", "modular_accum_floor",
           "modular_error_bound", "modular_eta", "modular_plan",
           "ozaki2_matmul", "ozaki2_matmul_batched",
           "ozaki2_matmul_complex", "ozaki2_matmul_df32",
           "residues_from_slices",
           "resolve_modular", "select_moduli", "usable_moduli"]

# Past 2 * 53 bits even a double-double reference is matched; the cap
# bounds the moduli pool the same way accuracy.MAX_SPLITS bounds s.
MAX_BETA = 112

_INT32_MAX = 2 ** 31 - 1


def _odd_primes_desc(limit: int = 251) -> tuple[int, ...]:
    sieve = np.ones(limit + 1, dtype=bool)
    sieve[:2] = False
    for p in range(2, int(limit ** 0.5) + 1):
        if sieve[p]:
            sieve[p * p::p] = False
    return tuple(int(p) for p in np.flatnonzero(sieve)[::-1] if p % 2 == 1)


# Odd primes <= 251 descending: the int8 residue alphabet. 2 is excluded
# not for range but for uniqueness — balanced digits are unique only for
# odd moduli (an even modulus has two centered representatives of m/2).
MODULI_POOL = _odd_primes_desc()


@functools.lru_cache(maxsize=256)
def usable_moduli(k: int) -> tuple[int, ...]:
    """The moduli whose residue GEMM cannot overflow int32 at this k:
    ``k * ((m-1)/2)^2 <= 2^31 - 1`` (centered residues bound each
    product by ``((m-1)/2)^2``; the exact analogue of Eq. (3)/(4))."""
    if k <= 0:
        raise ValueError("k must be positive")
    return tuple(m for m in MODULI_POOL
                 if k * ((m - 1) // 2) ** 2 <= _INT32_MAX)


def select_moduli(k: int, beta: int) -> tuple[int, ...]:
    """Minimal descending-prime prefix with ``prod > 2 * k * 4^beta``.

    The CRT range requirement: ``|C_int| <= k * (2^beta - 1)^2``, and the
    balanced representation is unique over ``(-M/2, M/2)``, so ``M >
    2 k 4^beta`` guarantees exact reconstruction. Raises when the pool
    cannot cover the range (beta too large for this k).
    """
    need = 2 * k * 4 ** beta
    chosen: list[int] = []
    prod = 1
    for m in usable_moduli(k):
        if prod > need:
            break
        chosen.append(m)
        prod *= m
    if prod <= need:
        raise ValueError(
            f"moduli pool exhausted: k={k}, beta={beta} needs product > "
            f"2*k*4^beta (~2^{need.bit_length()}) but the usable odd primes "
            f"<= 251 reach only ~2^{prod.bit_length()}")
    return tuple(chosen)


# ----------------------------------------------------------------------------
# Guaranteed bounds (mirror core.accuracy.error_bound's structure)
# ----------------------------------------------------------------------------

def modular_eta(beta: int) -> float:
    """eta: ``|C - C_hat| <= k * eta * 2^{ea_i + eb_j}``, guaranteed.

    Truncation toward zero keeps ``|A - A_kept| <= 2^{ea - beta}`` with
    ``|A| < 2^{ea}``, so each k-term errs by at most
    ``(2 * 2^{-beta} + 4^{-beta}) * 2^{ea + eb}``.
    """
    if beta < 1:
        raise ValueError(f"beta must be >= 1, got {beta}")
    return 2.0 ** (1 - beta) + 4.0 ** (-beta)


def modular_accum_floor(beta: int, moduli: Sequence[int]) -> float:
    """Rounding floor of the FP64 CRT reconstruction (relative to
    ``2^{ea_i + eb_j}``) — the Scheme II ``accum_floor``.

    Every reconstruction term is bounded by ``M/2 * 4^{-beta}`` (so are
    all partial sums: balanced mixed-radix prefixes telescope), and each
    term costs <= 3 roundings at 2^-53 (``float(Q_j)``, the multiply,
    the add); +2 covers the final ldexp pair conservatively.
    """
    m_prod = 1
    for m in moduli:
        m_prod *= m
    term_cap = math.ldexp(float(m_prod), -(2 * beta + 1))
    return (3 * len(moduli) + 2) * 2.0 ** -53 * term_cap


def modular_error_bound(beta: int, k: int,
                        moduli: Optional[Sequence[int]] = None) -> float:
    """Total guaranteed ``max_ij |C - C_hat| / 2^{ea_i + eb_j}``."""
    if moduli is None:
        moduli = select_moduli(k, beta)
    return k * modular_eta(beta) + modular_accum_floor(beta, moduli)


def min_beta_for(target_error: float, k: int, *,
                 max_beta: int = MAX_BETA) -> int:
    """Smallest beta with ``k * modular_eta(beta) <= target_error``
    (clamped at ``max_beta``, mirroring ``accuracy.min_splits_for``)."""
    if target_error <= 0:
        raise ValueError(f"target_error must be > 0, got {target_error}")
    for beta in range(1, max_beta + 1):
        if k * modular_eta(beta) <= target_error:
            return beta
    return max_beta


# ----------------------------------------------------------------------------
# Operating point
# ----------------------------------------------------------------------------

class ModularPoint(NamedTuple):
    """One Scheme II operating point: mantissa bits, the split count that
    realizes them (``beta = num_splits * w``), and the residue moduli."""

    beta: int
    num_splits: int
    moduli: tuple[int, ...]


def resolve_modular(k: int, *, beta: Optional[int] = None,
                    target_error: Optional[float] = None,
                    num_moduli: Optional[int] = None, w: int = 7,
                    mantissa_space: int = DGEMM_MANTISSA_SPACE
                    ) -> ModularPoint:
    """Resolve the Scheme II accuracy knobs into a concrete point.

    Priority mirrors Scheme I's ``resolve_accuracy``:

    * explicit ``beta`` wins (rounded up to a slice multiple ``s * w`` —
      the integerization is slice-built, so only multiples of w exist);
    * else ``target_error`` sizes beta via the guaranteed bound;
    * else the paper's DGEMM mantissa space (70 bits — the same default
      Scheme I's ``select_num_splits`` targets).

    ``num_moduli`` pins the GEMM count (the ``ozaki2-fp64xL`` spec dial):
    with no beta/target it sizes beta UP to the largest count those L
    primes can reconstruct (the accuracy dial, mirroring pinned s); with
    a beta/target it must still cover the range — fewer moduli than the
    CRT needs is not graceful degradation but wraparound garbage, so
    that is a ``ValueError``, never a silent fallback.
    """
    pool = usable_moduli(k)
    if num_moduli is not None:
        if num_moduli < 1:
            raise ValueError(f"num_moduli must be >= 1, got {num_moduli}")
        if num_moduli > len(pool):
            raise ValueError(
                f"num_moduli={num_moduli} exceeds the {len(pool)} usable "
                f"odd-prime moduli at k={k}")
    if beta is None:
        if target_error is not None:
            beta = min_beta_for(target_error, k)
        elif num_moduli is not None:
            # pinned GEMM count: the largest beta those primes reconstruct
            moduli = pool[:num_moduli]
            cap = 1
            for m in moduli:
                cap *= m
            s = 0
            while (s + 1) * w <= MAX_BETA and cap > 2 * k * 4 ** ((s + 1) * w):
                s += 1
            if s < 1:
                raise ValueError(
                    f"num_moduli={num_moduli} covers no mantissa bits at "
                    f"k={k}: product ~2^{cap.bit_length()} <= 2*k*4^{w}")
            return ModularPoint(s * w, s, tuple(moduli))
        else:
            beta = mantissa_space
    s = -(-beta // w)
    beta = s * w
    if beta > MAX_BETA:
        raise ValueError(f"beta={beta} exceeds MAX_BETA={MAX_BETA}")
    minimal = select_moduli(k, beta)
    if num_moduli is None:
        moduli = minimal
    else:
        if num_moduli < len(minimal):
            raise ValueError(
                f"num_moduli={num_moduli} cannot reconstruct beta={beta} "
                f"at k={k}: the CRT needs >= {len(minimal)} moduli "
                f"(fewer is wraparound, not graceful degradation)")
        moduli = pool[:num_moduli]
    if target_error is not None and \
            k * modular_eta(beta) > target_error:
        raise ValueError(
            f"target_error={target_error} unreachable at beta={beta} "
            f"(k * eta = {k * modular_eta(beta):.3g})")
    return ModularPoint(beta, s, moduli)


# ----------------------------------------------------------------------------
# Residue arithmetic (device-side, every integer stage exact)
# ----------------------------------------------------------------------------

def _mods_array(moduli: Sequence[int], ndim: int) -> jnp.ndarray:
    """int32 moduli broadcast against an (ell, ...) residue stack."""
    m = jnp.asarray(np.asarray(moduli, np.int32))
    return m.reshape((len(moduli),) + (1,) * (ndim - 1))


def center_mod(x: jax.Array, moduli: Sequence[int]) -> jax.Array:
    """Centered residues of an (ell, ...) int32 stack: x[j] mod m_j in
    ``[-(m_j-1)/2, (m_j-1)/2]`` (floor-mod then fold the upper half)."""
    mods = _mods_array(moduli, x.ndim)
    r = jnp.mod(x, mods)
    return r - jnp.where(r > (mods - 1) // 2, mods, 0)


def residues_from_slices(slices: jax.Array, w: int,
                         moduli: Sequence[int]) -> jax.Array:
    """int8 centered residues of the integerized operand, per modulus.

    slices: (s, ..., k) int8 from ``split_int`` (most significant first),
    representing ``A_int = sum_p slices[p] * 2^{(s-1-p)w}``. The weights
    ``2^{(s-1-p)w} mod m_j`` are host-side pow-mod (exact python ints);
    the device does one int32 tensordot (bounded by ``s * 127 * 250``)
    and one centered mod. Returns (ell, ..., k) int8.
    """
    s = slices.shape[0]
    wts = np.array([[pow(2, (s - 1 - p) * w, m) for p in range(s)]
                    for m in moduli], np.int32)
    x = jnp.tensordot(jnp.asarray(wts), slices.astype(jnp.int32),
                      axes=[[1], [0]])
    return center_mod(x, moduli).astype(jnp.int8)


def _garner_tables(moduli: Sequence[int]):
    """Host-side Garner constants: prefix products Q_j (python ints),
    ``Q_j^{-1} mod m_j``, and ``Q_i mod m_j`` for i < j."""
    ell = len(moduli)
    prefix = [1]
    for m in moduli[:-1]:
        prefix.append(prefix[-1] * m)
    inv = [pow(prefix[j] % moduli[j], -1, moduli[j]) for j in range(ell)]
    qmod = [[prefix[i] % moduli[j] for j in range(ell)] for i in range(ell)]
    return prefix, inv, qmod


def crt_digits(cres: jax.Array, moduli: Sequence[int]) -> list[jax.Array]:
    """Balanced mixed-radix digits of the value behind the residues.

    cres: (ell, ...) int32 centered residues of one integer X with
    ``|X| < M/2``. Garner's recurrence, digits centered per modulus:
    ``X = sum_j v_j * Q_j`` with ``|v_j| <= (m_j-1)/2`` — unique for odd
    moduli, so the digits ARE X's balanced representation (exactness is
    an identity, not an approximation). All int32: the inner sum is
    bounded by ``125 + ell * 125 * 250 < 2^21``.
    """
    _, inv, qmod = _garner_tables(moduli)
    digits: list[jax.Array] = []
    for j, mj in enumerate(moduli):
        acc = cres[j]
        for i in range(j):
            acc = acc - digits[i] * jnp.int32(qmod[i][j])
        d = jnp.mod(acc, jnp.int32(mj))
        v = jnp.mod(d * jnp.int32(inv[j]), jnp.int32(mj))
        digits.append(v - jnp.where(v > (mj - 1) // 2, jnp.int32(mj), 0))
    return digits


def _split26(x: float) -> tuple[float, float]:
    """Veltkamp split of a host f64 into an exact (hi, lo) pair with
    <= 26 / 27 significant bits each (``x == hi + lo`` exactly)."""
    c = x * (2.0 ** 27 + 1.0)
    hi = c - (c - x)
    return hi, x - hi


def crt_value(digits: Sequence[jax.Array], moduli: Sequence[int], beta: int,
              e_base: jax.Array) -> jax.Array:
    """FP64 reconstruction: ``ldexp(sum_j v_j * float(Q_j) * 4^{-beta},
    ea + eb)``, summed smallest radix first (ascending j) so rounding
    stays within ``modular_accum_floor``. ``float(Q_j)`` rounds at
    2^-53 relative — covered by the floor, like every term op.

    Each scale is Veltkamp-split host-side into an exact (hi, lo) pair
    of <= 27-bit halves, so both ``v * lo`` and ``v * hi`` products are
    EXACT in f64 (|v| <= 125 adds 7 bits: 34 < 53) and only the running
    adds round. That makes the sum FMA-contraction-proof — fusing an
    exact mul into the following add cannot move a bit — so jit, eager,
    and the fused-CRT kernel epilogue produce the identical bit pattern
    (the same trick the Scheme I epilogue gets for free from its
    power-of-two scale)."""
    prefix, _, _ = _garner_tables(moduli)
    c = None
    for j, v in enumerate(digits):
        hi, lo = _split26(math.ldexp(float(prefix[j]), -2 * beta))
        vf = v.astype(jnp.float64)
        t_lo = vf * lo                       # exact: 7 + 27 bits
        c = t_lo if c is None else c + t_lo  # smallest piece first
        c = c + vf * hi                      # exact: 7 + 26 bits
    return ldexp(c, e_base)


def garner_constants(moduli: Sequence[int], beta: int):
    """Static Garner constants for the fused-CRT epilogue kernel: the
    moduli, ``Q_i mod m_j`` rows, ``Q_j^{-1} mod m_j``, and the per-digit
    FP64 scales ``float(Q_j) * 4^{-beta}`` as Veltkamp (hi, lo) pairs
    (``crt_value``'s exact-product form) — every value a hashable python
    scalar, so the kernel wrapper can take them as jit statics and
    replay ``crt_digits``/``crt_value``'s exact arithmetic in VMEM."""
    prefix, inv, qmod = _garner_tables(moduli)
    scales = tuple(_split26(math.ldexp(float(prefix[j]), -2 * beta))
                   for j in range(len(moduli)))
    return (tuple(moduli), tuple(tuple(row) for row in qmod),
            tuple(inv), scales)


def crt_value_dw(digits: Sequence[jax.Array], moduli: Sequence[int],
                 beta: int, e_base: jax.Array):
    """df32 reconstruction target: the CRT sum accumulated in double-
    float32 (DW) arithmetic — no FP64 hardware needed past the exact
    integer stages.

    Each scale ``Q_j * 4^{-beta}`` is decomposed host-side into an exact
    (f32 hi, f32 lo) pair; the digit (|v| <= 125, exact in f32)
    multiplies it via the Dekker-based ``dw_mul_single`` and the terms
    accumulate ascending-radix through ``dw_add``. Low-radix scales
    below f32's subnormal floor round to zero — they sit ~2^-90 under
    the df32 accumulation floor, so the guaranteed df32-level bound is
    unaffected. Returns a ``DW`` pair scaled by ``2^{e_base}``.
    """
    from .xmath import DW, dw_add, dw_mul_single
    prefix, _, _ = _garner_tables(moduli)
    c = None
    for j, v in enumerate(digits):
        scale = math.ldexp(float(prefix[j]), -2 * beta)
        hi = np.float32(scale)
        lo = np.float32(scale - float(hi))
        scale_dw = DW(jnp.float32(hi), jnp.float32(lo))
        term = dw_mul_single(scale_dw, v.astype(jnp.float32))
        c = term if c is None else dw_add(c, term)
    return DW(ldexp(c.hi, e_base), ldexp(c.lo, e_base))


# ----------------------------------------------------------------------------
# Config + plan reflection
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModularConfig:
    """Configuration for one Scheme II GEMM (the ``OzakiConfig`` sibling).

    beta:         mantissa bits kept per operand (rounded up to ``s*w``),
                  or None to derive from ``target_error`` / the 70-bit
                  DGEMM default.
    target_error: accuracy target on the scaled error (same contract as
                  Scheme I's) — sizes beta via the guaranteed bound.
    num_moduli:   pinned residue-GEMM count L (``ozaki2-fp64xL``): with
                  no beta/target it is the accuracy dial (largest beta L
                  primes reconstruct); with one it must cover the range.
    w:            bits per integerization slice (int8 keeps 7).
    backend:      "xla" | "pallas" | "pallas_fused" — residue GEMMs as a
                  batched dot_general or the batch-grid Pallas kernel
                  (pallas_fused additionally splits with the one-pass
                  kernel).
    fuse_epilogue: with ``pallas_fused``: run the balanced-Garner CRT
                  reconstruction inside the residue GEMM grid's epilogue
                  (VMEM scratch over the modulus axis) — int32 residue
                  products never round-trip through HBM.
    interpret:    Pallas interpret mode; None takes the host default
                  (``kernels.ops.INTERPRET``: on off a TPU, off on one).
    tile:         optional TilePlan for the kernel launches.
    """

    beta: Optional[int] = None
    target_error: Optional[float] = None
    num_moduli: Optional[int] = None
    w: int = 7
    backend: str = "xla"
    fuse_epilogue: bool = False
    interpret: Optional[bool] = None
    tile: Optional[TilePlan] = None

    def point(self, k: int) -> ModularPoint:
        return resolve_modular(k, beta=self.beta,
                               target_error=self.target_error,
                               num_moduli=self.num_moduli, w=self.w)

    def plan(self, k: int, *, batch_layout: str = "none") -> PipelinePlan:
        return modular_plan(k, point=self.point(k), backend=self.backend,
                            fuse_epilogue=self.fuse_epilogue,
                            interpret=self.interpret, tile=self.tile,
                            batch_layout=batch_layout)


def modular_plan(k: int, *, point: Optional[ModularPoint] = None,
                 backend: str = "xla", fuse_epilogue: bool = False,
                 interpret: Optional[bool] = None,
                 tile: Optional[TilePlan] = None,
                 batch_layout: str = "none",
                 target_error: Optional[float] = None,
                 num_moduli: Optional[int] = None) -> PipelinePlan:
    """The ``PipelinePlan`` one Scheme II operating point executes as.

    The plan records the point (``beta``, ``num_moduli``, and
    ``num_splits`` = the integerization slice count) next to the launch
    knobs, so the plan cache round-trips everything the executor needs:
    the moduli themselves are re-derived deterministically
    (``usable_moduli(k)[:num_moduli]`` — always a pool prefix).
    """
    if point is None:
        point = resolve_modular(k, target_error=target_error,
                                num_moduli=num_moduli)
    if tile is None:
        tile = TilePlan(num_splits=point.num_splits, concat_k=False)
    if fuse_epilogue and backend != "pallas_fused":
        raise ValueError(
            f"fuse_epilogue (fused-CRT reconstruction) needs the "
            f"pallas_fused backend, got backend={backend!r}")
    if backend == "pallas_fused":
        fusion = "epilogue" if fuse_epilogue else "stages"
    else:
        fusion = "none"
    return PipelinePlan(
        scheme="ozaki2_fp64", num_splits=point.num_splits,
        beta=point.beta, num_moduli=len(point.moduli), tile=tile,
        backend=backend, fusion=fusion,
        batch_layout=batch_layout, pair_policy="full", fuse_diagonals=True,
        concat_k=False, full_pairs=False, accum="f64", interpret=interpret)


# ----------------------------------------------------------------------------
# Drivers (mirror core.ozaki's thin-driver role)
# ----------------------------------------------------------------------------

def _e_base(ea: jax.Array, eb: jax.Array) -> jax.Array:
    """Deferred per-element exponent: broadcast outer sum (int32)."""
    return (ea[..., :, None].astype(jnp.int32) +
            eb[..., None, :].astype(jnp.int32))


def _check_f64(a, b, name: str) -> None:
    if a.dtype != jnp.float64 or b.dtype != jnp.float64:
        raise TypeError(
            f"{name} takes float64 operands (complex128 routes through "
            f"ozaki2_matmul_complex, float32 through ozaki2_matmul_df32), "
            f"got {a.dtype} @ {b.dtype}")
    refuse_f64_on_tpu(name)


def ozaki2_matmul(a: jax.Array, b: jax.Array,
                  cfg: ModularConfig = ModularConfig()) -> jax.Array:
    """FP64-accurate ``C = A @ B`` via residue-system int8 GEMMs.

    A: (m, k) f64, B: (k, n) f64 — the Scheme II sibling of
    ``ozaki_matmul``, with ``len(point.moduli)`` int8 GEMMs instead of
    ``s(s+1)/2`` slice pairs.
    """
    _check_f64(a, b, "ozaki2_matmul")
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"ozaki2_matmul expects 2-D operands, got "
                         f"{a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape}")
    k = a.shape[1]
    plan = cfg.plan(k)
    from .executors import get_executor          # lazy: executors import us
    ex = get_executor(plan)
    w = cfg.w
    sa = ex.split(a, w)
    sb = ex.split(b.T, w)
    return ex.contract(sa, sb, w, _e_base(sa.exp, sb.exp),
                       (a.shape[0], b.shape[1]))


def _fold_rows2(split_fn, x3: jax.Array, w: int) -> SplitResult:
    """Split a (B, r, k) stack by folding the batch into rows (exact:
    exponents and slices are row-independent)."""
    bsz, r, k = x3.shape
    res = split_fn(x3.reshape(bsz * r, k), w)
    s = res.slices.shape[0]
    return SplitResult(res.slices.reshape(s, bsz, r, k),
                       res.exp.reshape(bsz, r), res.w)


@functools.partial(jax.custom_jvp, nondiff_argnums=(2,))
def _batched_core2(a: jax.Array, b: jax.Array,
                   cfg: ModularConfig) -> jax.Array:
    if b.ndim == 2:
        # broadcast weights: fold the batch into rows (row-independent
        # split/exponents — equals a loop over ozaki2_matmul bitwise)
        bsz, m, k = a.shape
        out = ozaki2_matmul(a.reshape(bsz * m, k), b, cfg)
        return out.reshape(bsz, m, b.shape[1])
    bsz, m, k = a.shape
    n = b.shape[-1]
    plan = cfg.plan(k, batch_layout="grid")
    from .executors import get_executor          # lazy: executors import us
    ex = get_executor(plan)
    w = cfg.w
    sa = _fold_rows2(ex.split, a, w)
    sb = _fold_rows2(ex.split, jnp.swapaxes(b, 1, 2), w)
    return ex.contract(sa, sb, w, _e_base(sa.exp, sb.exp), (bsz, m, n))


@_batched_core2.defjvp
def _batched_core2_jvp(cfg, primals, tangents):
    a, b = primals
    da, db = tangents
    primal = _batched_core2(a, b, cfg)
    # exact-product rule, same rationale as the Scheme I batched JVP
    tangent = (jnp.matmul(da, b, preferred_element_type=a.dtype) +
               jnp.matmul(a, db, preferred_element_type=a.dtype))
    return primal, tangent.astype(primal.dtype)


def ozaki2_matmul_batched(a: jax.Array, b: jax.Array,
                          cfg: ModularConfig = ModularConfig()) -> jax.Array:
    """Batched Scheme II GEMM: ``C[i] = A[i] @ B[i]`` (or shared ``B``).

    a: (B, m, k) f64; b: (B, k, n) stacked or (k, n) broadcast. Stacked
    batches fold the (modulus, batch) product onto the batch-grid GEMM
    kernel's leading dimension — one launch for all ``ell * B`` residue
    GEMMs. Differentiable via the exact-product JVP.
    """
    if a.ndim != 3:
        raise ValueError(f"a must be (batch, m, k), got {a.shape}")
    if b.ndim not in (2, 3):
        raise ValueError(f"b must be (k, n) or (batch, k, n), got {b.shape}")
    if b.ndim == 3 and a.shape[0] != b.shape[0]:
        raise ValueError(f"batch mismatch: {a.shape} vs {b.shape}")
    if a.shape[-1] != b.shape[-2]:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape}")
    _check_f64(a, b, "ozaki2_matmul_batched")
    return _batched_core2(a, b, cfg)


# ----------------------------------------------------------------------------
# Complex + df32 routes (the Scheme I parity surfaces, PR 9)
# ----------------------------------------------------------------------------

def ozaki2_matmul_complex(a: jax.Array, b: jax.Array,
                          cfg: ModularConfig = ModularConfig(),
                          algo: str = "4mul") -> jax.Array:
    """complex128 ``C = A @ B`` through real Scheme II GEMMs.

    The same decomposition ``ozaki_matmul_complex`` uses — the scheme
    only changes what a *real* GEMM costs, not the complex algebra:

    ``algo="4mul"``: Cr = ArBr - AiBi, Ci = ArBi + AiBr (each of the 4
    real matrices integerized exactly once, residue stacks reused).
    ``algo="3mul"``: Karatsuba, one fewer residue-GEMM group at one
    extra magnitude bit on the summed operands (covered by beta).
    """
    refuse_f64_on_tpu("ozaki2_matmul_complex")
    if a.dtype != jnp.complex128 or b.dtype != jnp.complex128:
        raise TypeError(f"ozaki2_matmul_complex takes complex128 operands, "
                        f"got {a.dtype} @ {b.dtype}")
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"complex operands must be 2-D, got "
                         f"{a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape}")
    ar, ai = jnp.real(a), jnp.imag(a)
    br, bi = jnp.real(b), jnp.imag(b)
    k = a.shape[1]
    plan = cfg.plan(k)
    from .executors import get_executor          # lazy: executors import us
    ex = get_executor(plan)
    w = cfg.w

    def real_mm(xs, ys, shape):
        return ex.contract(xs, ys, w, _e_base(xs.exp, ys.exp), shape)

    shape = (a.shape[0], b.shape[1])
    if algo == "3mul":
        s_ar = ex.split(ar, w)
        s_ai = ex.split(ai, w)
        s_as = ex.split(ar + ai, w)
        s_br = ex.split(br.T, w)
        s_bi = ex.split(bi.T, w)
        s_bs = ex.split((br + bi).T, w)
        p1 = real_mm(s_ar, s_br, shape)
        p2 = real_mm(s_ai, s_bi, shape)
        p3 = real_mm(s_as, s_bs, shape)
        return jax.lax.complex(p1 - p2, p3 - p1 - p2)
    if algo != "4mul":
        raise ValueError(f"algo must be '4mul' or '3mul', got {algo!r}")
    s_ar = ex.split(ar, w)
    s_ai = ex.split(ai, w)
    s_br = ex.split(br.T, w)
    s_bi = ex.split(bi.T, w)
    c_r = real_mm(s_ar, s_br, shape) - real_mm(s_ai, s_bi, shape)
    c_i = real_mm(s_ar, s_bi, shape) + real_mm(s_ai, s_br, shape)
    return jax.lax.complex(c_r, c_i)


def ozaki2_matmul_df32(a: jax.Array, b: jax.Array,
                       cfg: ModularConfig = ModularConfig()) -> jax.Array:
    """f32-in/f32-out Scheme II GEMM with a df32 reconstruction target.

    Every stage up to the CRT digits is exact integer arithmetic on the
    *widened* operands (f32 -> f64 is exact), identical to
    ``ozaki2_matmul``'s stages; the reconstruction then runs
    ``crt_value_dw`` — the CRT sum in double-float32 — instead of the
    FP64 sum, so past the integer stages the route needs no FP64
    hardware. Returns ``dw_to_single`` of the DW result (f32).
    """
    # the CRT sum is df32, but the split widens to f64 first
    refuse_f64_on_tpu("ozaki2_matmul_df32")
    if a.dtype != jnp.float32 or b.dtype != jnp.float32:
        raise TypeError(f"ozaki2_matmul_df32 takes float32 operands, got "
                        f"{a.dtype} @ {b.dtype}")
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"ozaki2_matmul_df32 expects 2-D operands, got "
                         f"{a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape}")
    k = a.shape[1]
    plan = cfg.plan(k)
    from .executors import get_executor          # lazy: executors import us
    from .xmath import dw_to_single
    ex = get_executor(plan)
    w = cfg.w
    sa = ex.split(a.astype(jnp.float64), w)      # exact widening
    sb = ex.split(b.T.astype(jnp.float64), w)
    moduli = usable_moduli(k)[:plan.num_moduli]
    ra = residues_from_slices(sa.slices, w, moduli)
    rb = residues_from_slices(sb.slices, w, moduli)
    p = ex.gemm(ra, rb)
    digits = crt_digits(center_mod(p, moduli), moduli)
    out = crt_value_dw(digits, moduli, plan.beta,
                       _e_base(sa.exp, sb.exp))
    return dw_to_single(out)
