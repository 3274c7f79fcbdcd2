"""Accuracy-adaptive planning: error bounds for split counts and pair
truncation (the "fast mode" of the follow-up literature).

The paper's pipeline pays for a fixed ``num_splits`` s regardless of the
input, yet its own accuracy experiments (Fig. 6, Fig. 7) show the
required s varies sharply with the data. Two follow-ups close that gap:
Uchino, Ozaki & Imamura (arXiv:2409.13313) *reduce* the split count per
input with an accuracy guarantee and add a *fast mode* that skips
low-order slice-pair products; Abdelfattah et al. (arXiv:2506.11277)
supply the error bounds that make the truncation principled. This module
implements both bound families; ``core.tuning`` / ``core.ozaki`` consume
them to resolve ``target_error`` / ``fast_mode`` / ``pair_policy`` knobs
into a concrete ``(num_splits, pair_policy)`` operating point.

The error model
---------------

Slice ``p`` of A is bounded by ``|A_p slice value| < 2^{ea_i - p*w}``
(the shared row exponent ``2^{ea}`` strictly dominates the row, and each
slice keeps ``w`` bits). Hence the slice-pair product (p, q), summed
over the reduction dim k, contributes at most

    |sum_k A_p B_q|  <  k * 2^{ea_i + eb_j} * 2^{-(p+q) * w}.

Every error source of the scheme — the split tails (slices p >= s), the
schedule's dropped diagonals (the paper computes pairs with
``p + q <= s - 1`` only), and fast-mode pair truncation — is exactly "a
set of (p, q) pairs not computed", so the guaranteed bound is a single
geometric sum over the *complement* of the kept pair set:

    |C - C_hat|_ij  <=  k * eta * 2^{ea_i + eb_j},
    eta = sum_{(p, q) not kept} 2^{-(p+q) * w}          (truncation_eta)

plus a small accumulation-rounding floor (``accum_floor``) that no split
count can remove. ``scaled_error`` measures exactly the left-hand side
normalization, so benchmarks and tests can *prove* the bound holds.

The data-dependent refinement (``required_splits``): an element with
exponent ``e`` under row exponent ``ea`` carries no mantissa bits below
``e - mantissa_bits``, so slices with ``p * w >= spread + mantissa_bits``
are identically zero — pairs touching them contribute nothing. Narrow
row/column exponent spreads therefore shrink the effective pair grid and
admit *fewer* splits at the same guaranteed error (the follow-up's
"accuracy-guaranteed split reduction"). All-zero rows/columns are
clamped to spread 0 (finite sentinel — see ``splitting.row_exponents``),
so zero-cancellation inputs never produce ``-inf``/NaN statistics.

Everything here is host-side, closed-form float arithmetic over static
shapes: resolution happens once per GEMM shape (trace-safe), never on
the device.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

# core.modular imports tuning/splitting only (never this module), so the
# top-level import is cycle-free; it powers the cross-scheme cost model.
from .modular import modular_eta, resolve_modular
from .splitting import row_exponents, slice_width
from .tuning import diagonal_groups, hbm_pass_model, parse_pair_policy
from .xmath import exponent

__all__ = ["MAX_SPLITS", "SchemeChoice", "kept_pairs", "truncation_eta",
           "input_truncation_eta", "accum_floor", "error_bound",
           "min_splits_for", "pair_budget_for", "plan_meets_target",
           "resolve_accuracy", "scheme_costs", "exponent_spread",
           "required_splits", "scaled_error"]

MAX_SPLITS = 26     # ceil(2 * 53 / 4): past this even INT4 covers dd64

# Accumulation-rounding floor per accumulation group, relative to the
# k * 2^{ea+eb} normalizer: f64 rounds at 2^-53 per add; the compensated
# df32 pair carries ~48 bits — 2^-44 is a deliberately generous cover.
_ACCUM_UNIT = {"f64": 2.0 ** -52, "df32": 2.0 ** -44}


# ----------------------------------------------------------------------------
# Guaranteed (shape-only) bounds
# ----------------------------------------------------------------------------

def kept_pairs(num_splits: int, *, pair_policy: str = "full",
               full_pairs: bool = False) -> list[tuple[int, int]]:
    """The (p, q) slice pairs a schedule actually computes."""
    budget = parse_pair_policy(pair_policy, num_splits, full_pairs)
    return [(p, q)
            for _, pairs in diagonal_groups(num_splits, full_pairs,
                                            pair_budget=budget)
            for p, q in pairs]


def truncation_eta(num_splits: int, w: int, *, pair_policy: str = "full",
                   full_pairs: bool = False) -> float:
    """eta: |C - C_hat| <= k * eta * 2^{ea_i + eb_j}, guaranteed.

    The sum over ALL dropped pairs — split tails (p >= s or q >= s),
    schedule-dropped diagonals, and fast-mode truncation. Summed over
    the *dropped* set directly (per-diagonal deficits plus the closed-
    form geometric tail), never as total-minus-kept: that subtraction
    cancels ~7 decimal digits and would corrupt tight targets.
    """
    r = 2.0 ** (-w)
    kept = kept_pairs(num_splits, pair_policy=pair_policy,
                      full_pairs=full_pairs)
    kept_per_t: dict[int, int] = {}
    for p, q in kept:
        kept_per_t[p + q] = kept_per_t.get(p + q, 0) + 1
    t_cut = max(kept_per_t) + 1
    # diagonal t holds t + 1 pairs over the full (infinite-slice) grid
    head = math.fsum(((t + 1) - kept_per_t.get(t, 0)) * r ** t
                     for t in range(t_cut))
    tail = r ** t_cut * (t_cut * (1.0 - r) + 1.0) / (1.0 - r) ** 2
    return head + tail


def input_truncation_eta(num_splits: int, w: int, sa_eff: int, sb_eff: int,
                         *, pair_policy: str = "full",
                         full_pairs: bool = False) -> float:
    """Per-input eta: slices beyond the operands' information content are
    identically zero, so only dropped pairs with ``p < sa_eff`` and
    ``q < sb_eff`` contribute (``sa_eff/sb_eff`` from exponent spreads).
    """
    r = 2.0 ** (-w)
    kept = set(kept_pairs(num_splits, pair_policy=pair_policy,
                          full_pairs=full_pairs))
    return math.fsum(r ** (p + q)
                     for p in range(sa_eff) for q in range(sb_eff)
                     if (p, q) not in kept)


def accum_floor(num_splits: int, k: int, *, accum: str = "f64",
                fuse_diagonals: bool = True, pair_policy: str = "full",
                full_pairs: bool = False) -> float:
    """Rounding floor of the high-precision accumulation stage (relative
    to ``2^{ea_i + eb_j}``): no split count or pair budget removes it."""
    budget = parse_pair_policy(pair_policy, num_splits, full_pairs)
    groups = diagonal_groups(num_splits, full_pairs, pair_budget=budget)
    g = len(groups) if fuse_diagonals else sum(len(p) for _, p in groups)
    return (g + 2) * _ACCUM_UNIT[accum] * k


def error_bound(num_splits: int, w: int, k: int, *,
                pair_policy: str = "full", full_pairs: bool = False,
                accum: str = "f64", fuse_diagonals: bool = True) -> float:
    """Total guaranteed bound on ``max_ij |C - C_hat| / 2^{ea_i+eb_j}``."""
    return (k * truncation_eta(num_splits, w, pair_policy=pair_policy,
                               full_pairs=full_pairs)
            + accum_floor(num_splits, k, accum=accum,
                          fuse_diagonals=fuse_diagonals,
                          pair_policy=pair_policy, full_pairs=full_pairs))


# ----------------------------------------------------------------------------
# Operating-point selection (shape-only: trace-safe)
# ----------------------------------------------------------------------------

def min_splits_for(target_error: float, k: int, *, ell_acc: int = 31,
                   ell_in: int = 7, fuse: bool = True,
                   full_pairs: bool = False,
                   max_splits: int = MAX_SPLITS) -> int:
    """Smallest s whose guaranteed truncation error meets the target.

    ``target_error`` bounds ``k * truncation_eta`` (the part s controls;
    the accumulation floor is reported separately by ``error_bound``).
    The slice width is re-derived per candidate s — fewer splits reserve
    less diagonal-fusion headroom, so w can widen as s shrinks.
    """
    if target_error <= 0:
        raise ValueError(f"target_error must be > 0, got {target_error}")
    for s in range(1, max_splits + 1):
        w = slice_width(k, ell_acc=ell_acc, ell_in=ell_in,
                        fuse_terms=s if fuse else 1)
        if k * truncation_eta(s, w, full_pairs=full_pairs) <= target_error:
            return s
    return max_splits


def pair_budget_for(target_error: float, num_splits: int, w: int, k: int,
                    *, full_pairs: bool = False) -> str:
    """Smallest pair budget still meeting the target at this s.

    Returns ``"budget:N"`` with minimal N, or ``"full"`` when no pair can
    be dropped without crossing the target (no truncation headroom).
    """
    if target_error <= 0:
        raise ValueError(f"target_error must be > 0, got {target_error}")
    total = len(kept_pairs(num_splits, full_pairs=full_pairs))
    for n in range(1, total):
        eta = truncation_eta(num_splits, w, pair_policy=f"budget:{n}",
                             full_pairs=full_pairs)
        if k * eta <= target_error:
            return f"budget:{n}"
    return "full"


def plan_meets_target(plan, k: int, target_error: float, *,
                      ell_acc: int = 31, ell_in: int = 7) -> bool:
    """Does a ``PipelinePlan``'s operating point guarantee the target?

    The acceptance rule for cached plans under a pinned ``target_error``:
    the target is the contract, not one specific ``(s, policy)`` string —
    a measured winner with MORE pairs or splits than the minimal resolved
    point still satisfies it (and must be accepted, or every cache hit
    would re-tune forever). Scheme II plans are judged on THEIR
    guaranteed bound (``k * modular_eta(beta)``) — under a target the
    two families are interchangeable contracts.
    """
    if getattr(plan, "scheme", "ozaki_fp64") == "ozaki2_fp64":
        return k * modular_eta(plan.beta) <= target_error
    fuse = plan.fuse_diagonals or plan.concat_k
    w = slice_width(k, ell_acc=ell_acc, ell_in=ell_in,
                    fuse_terms=plan.num_splits if fuse else 1)
    eta = truncation_eta(plan.num_splits, w, pair_policy=plan.pair_policy,
                         full_pairs=plan.full_pairs)
    return k * eta <= target_error


@dataclasses.dataclass(frozen=True)
class SchemeChoice:
    """One arbitrated cross-scheme operating point (hashable).

    ``scheme`` names the winning family; the family's knobs follow
    (Scheme I: ``num_splits``/``pair_policy``; Scheme II: ``beta``/
    ``num_moduli``, with ``num_splits`` the integerization slice count).
    ``gemms`` is the winner's modeled int8-GEMM-equivalent cost and
    ``costs`` records every candidate's, so callers (and tests) can see
    WHY the arbitration went the way it did. ``traffic`` records each
    candidate's modeled HBM passes (``tuning.hbm_pass_model`` at the
    family's best fused route) — the secondary axis: GEMM-equivalents
    rank first, traffic breaks cost ties before the incumbent rule does.
    """

    scheme: str
    num_splits: int
    pair_policy: str = "full"
    beta: int = 0
    num_moduli: int = 0
    gemms: float = 0.0
    costs: tuple = ()        # ((scheme, modeled cost), ...)
    traffic: tuple = ()      # ((scheme, modeled HBM passes), ...)


def _scheme2_cost(num_moduli: int, num_splits: int, k: int,
                  m: Optional[int], n: Optional[int]) -> float:
    """Scheme II's modeled cost in int8-GEMM equivalents (2mnk ops each).

    The residue GEMMs are the linear term (``ell`` launches); the CRT
    reconstruction is an O(ell^2) elementwise pass over the (m, n)
    output (``ell^2 / 2k`` GEMM-equivalents) and, when the output shape
    is known, the residue extraction tensordots add
    ``ell * s * (m + n) / 2mn`` — both vanish for tall-k shapes, which
    is exactly where Scheme II's linear GEMM count wins.
    """
    cost = float(num_moduli) + num_moduli ** 2 / (2.0 * k)
    if m is not None and n is not None:
        cost += num_moduli * num_splits * (m + n) / (2.0 * m * n)
    return cost


def scheme_costs(k: int, num_splits: int, *, target_error: Optional[float],
                 pair_policy: str = "full", full_pairs: bool = False,
                 m: Optional[int] = None,
                 n: Optional[int] = None) -> tuple:
    """Both families' modeled costs at MATCHED accuracy.

    Scheme I at the resolved ``(s, policy)`` costs its kept-pair count.
    Scheme II is sized for the same contract — the explicit
    ``target_error`` when one is set, else Scheme I's own guaranteed
    truncation bound (so a no-target comparison is still
    accuracy-matched, not apples-to-oranges). An infeasible Scheme II
    point (moduli pool exhausted) costs ``inf``.
    """
    cost_1 = float(len(kept_pairs(num_splits, pair_policy=pair_policy,
                                  full_pairs=full_pairs)))
    if target_error is None:
        w = slice_width(k, fuse_terms=num_splits)
        target_error = k * truncation_eta(num_splits, w,
                                          pair_policy=pair_policy,
                                          full_pairs=full_pairs)
    try:
        point = resolve_modular(k, target_error=target_error)
    except ValueError:
        return (("ozaki_fp64", cost_1), ("ozaki2_fp64", math.inf))
    cost_2 = _scheme2_cost(len(point.moduli), point.num_splits, k, m, n)
    return (("ozaki_fp64", cost_1), ("ozaki2_fp64", cost_2))


def resolve_accuracy(k: int, num_splits: int, *,
                     target_error: Optional[float] = None,
                     fast_mode: bool = False, pair_policy: str = "full",
                     ell_acc: int = 31, ell_in: int = 7, fuse: bool = True,
                     full_pairs: bool = False,
                     schemes: Optional[Sequence[str]] = None,
                     m: Optional[int] = None, n: Optional[int] = None):
    """Resolve the accuracy knobs into a concrete ``(s, pair_policy)``.

    * ``target_error`` REDUCES s below the configured operating point
      when the bound allows (never raises it — the configured s is the
      quality ceiling the caller asked for).
    * ``fast_mode`` truncates pairs: to the minimal budget meeting
      ``target_error`` when one is set, else to ``"diagonal"`` (drop the
      schedule's last, least-significant anti-diagonal — the follow-up
      paper's fast mode).
    * An explicit non-"full" ``pair_policy`` always wins over fast_mode.

    Idempotent: resolving an already-resolved point returns it unchanged.

    ``schemes`` turns the resolver into the CROSS-SCHEME cost model:
    pass the candidate families (e.g. ``("ozaki_fp64", "ozaki2_fp64")``)
    and the return type becomes a ``SchemeChoice`` — both families are
    sized for the same accuracy contract and the one with the fewer
    modeled int8-GEMM equivalents wins (``m``/``n`` refine Scheme II's
    elementwise overhead terms when the output shape is known). A cost
    tie falls through to modeled HBM traffic (``hbm_pass_model`` at each
    family's best fused route: Scheme I streaming vs the Scheme II
    fused-CRT epilogue); only a tie on BOTH axes goes to Scheme I, the
    bitwise-validated incumbent. Without ``schemes`` the legacy
    ``(s, policy)`` tuple contract is unchanged.
    """
    s = num_splits
    if target_error is not None:
        s = max(1, min(s, min_splits_for(target_error, k, ell_acc=ell_acc,
                                         ell_in=ell_in, fuse=fuse,
                                         full_pairs=full_pairs)))
    policy = pair_policy
    if policy == "full" and fast_mode:
        if target_error is not None:
            w = slice_width(k, ell_acc=ell_acc, ell_in=ell_in,
                            fuse_terms=s if fuse else 1)
            policy = pair_budget_for(target_error, s, w, k,
                                     full_pairs=full_pairs)
        else:
            policy = "diagonal"
    if schemes is None:
        return s, policy
    for name in schemes:
        if name not in ("ozaki_fp64", "ozaki2_fp64"):
            raise ValueError(f"unknown scheme {name!r} in schemes")
    costs = dict(scheme_costs(k, s, target_error=target_error,
                              pair_policy=policy, full_pairs=full_pairs,
                              m=m, n=n))
    # Secondary axis: modeled HBM passes at each family's best fused
    # route (Scheme I streaming vs the Scheme II fused-CRT epilogue) —
    # breaks GEMM-cost ties before the incumbent rule.
    traffic = {"ozaki_fp64": float(hbm_pass_model(
        s, fusion="streaming", pair_policy=policy)["total"])}
    point2 = None
    if math.isfinite(costs["ozaki2_fp64"]):
        if target_error is not None:
            point2 = resolve_modular(k, target_error=target_error)
        else:
            w = slice_width(k, ell_acc=ell_acc, ell_in=ell_in,
                            fuse_terms=s if fuse else 1)
            point2 = resolve_modular(
                k, target_error=k * truncation_eta(
                    s, w, pair_policy=policy, full_pairs=full_pairs))
        traffic["ozaki2_fp64"] = float(hbm_pass_model(
            point2.num_splits, fusion="epilogue", scheme="ozaki2_fp64",
            num_moduli=len(point2.moduli))["total"])
    else:
        traffic["ozaki2_fp64"] = math.inf
    ranked = sorted((name for name in schemes),
                    key=lambda name: (costs[name], traffic[name],
                                      name != "ozaki_fp64"))
    winner = ranked[0]
    all_costs = tuple((name, costs[name]) for name in schemes)
    all_traffic = tuple((name, traffic[name]) for name in schemes)
    if winner == "ozaki2_fp64" and math.isfinite(costs[winner]):
        return SchemeChoice(scheme="ozaki2_fp64",
                            num_splits=point2.num_splits, beta=point2.beta,
                            num_moduli=len(point2.moduli),
                            gemms=costs[winner], costs=all_costs,
                            traffic=all_traffic)
    return SchemeChoice(scheme="ozaki_fp64", num_splits=s,
                        pair_policy=policy, gemms=costs["ozaki_fp64"],
                        costs=all_costs, traffic=all_traffic)


# ----------------------------------------------------------------------------
# Data-dependent refinement (host-side, like core.auto_split)
# ----------------------------------------------------------------------------

def exponent_spread(m) -> jnp.ndarray:
    """Per-row exponent spread: row exponent minus the smallest *nonzero*
    element exponent, as int32 ``(rows,)``.

    Zero elements are clamped to the row exponent (no spread
    contribution) and all-zero rows — whose ``row_exponents`` sentinel is
    already finite — report spread 0, so zero-cancellation inputs never
    leak ``-inf`` into the exp2/ldexp scales downstream.
    """
    m = jnp.asarray(m)
    row_e = row_exponents(m)
    e = exponent(m)
    e = jnp.where(m != 0, e.astype(jnp.int32), row_e[:, None])
    return row_e - jnp.min(e, axis=-1).astype(jnp.int32)


def required_splits(a, b, *, target_error: Optional[float] = None,
                    mantissa_bits: int = 53, ell_acc: int = 31,
                    ell_in: int = 7, fuse: bool = True,
                    full_pairs: bool = False, pair_policy: str = "full",
                    max_splits: int = MAX_SPLITS) -> int:
    """Minimal s meeting ``target_error`` for THESE operands.

    ``a: (m, k)``, ``b: (k, n)`` — the spread statistics run on device
    (jitted ``frexp``/reductions), the decision on the host (it changes
    trace shapes, exactly like ``core.auto_split``). ``target_error=None``
    asks for input-exactness: the smallest s whose kept pairs cover every
    pair of informative slices.
    """
    k = a.shape[-1]
    sa = int(np.max(np.asarray(exponent_spread(a))))
    sb = int(np.max(np.asarray(exponent_spread(jnp.asarray(b).T))))
    tgt = 0.0 if target_error is None else float(target_error)
    for s in range(1, max_splits + 1):
        w = slice_width(k, ell_acc=ell_acc, ell_in=ell_in,
                        fuse_terms=s if fuse else 1)
        sa_eff = -(-(sa + mantissa_bits) // w)
        sb_eff = -(-(sb + mantissa_bits) // w)
        eta = input_truncation_eta(s, w, sa_eff, sb_eff,
                                   pair_policy=pair_policy,
                                   full_pairs=full_pairs)
        if k * eta <= tgt:
            return s
    return max_splits


def scaled_error(c, ref_hi, a, b, ref_lo=None) -> float:
    """Measured ``max_ij |c - ref| / 2^{ea_i + eb_j}`` — the exact
    normalization ``error_bound`` guarantees, so ``scaled_error <= bound``
    is a *provable* (and CSV-checkable) statement. ``ref_lo`` carries the
    low word of a double-double reference for sub-ulp resolution."""
    ea = np.asarray(row_exponents(jnp.asarray(a)))
    eb = np.asarray(row_exponents(jnp.asarray(b).T))
    diff = np.asarray(c) - np.asarray(ref_hi)
    if ref_lo is not None:
        diff = diff - np.asarray(ref_lo)
    return float(np.max(np.abs(diff) / np.exp2(ea[:, None] + eb[None, :])))
