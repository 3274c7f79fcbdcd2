"""Planner for the Ozaki pipeline: tiles, schedule, and execution strategy.

Two layers of planning live here:

* ``TilePlan`` / ``select_plan`` — block shapes and split count from
  operand shapes (the PR 1 tile planner, unchanged contract).
* ``PipelinePlan`` / ``plan_for`` / ``select_pipeline_plan`` — the full
  execution strategy for one GEMM shape: which executor runs the pipeline
  (``backend``), how stages are fused (``fusion``: separate kernels,
  stage-fused kernels, or the epilogue-fused GEMM that never materializes
  int32 products), how a batch is laid out (``batch_layout``: folded into
  rows, an explicit batch grid dimension, or absent), and which mesh axis
  the reduction is sharded over (``shard_axis``). ``core.ozaki`` is a
  thin driver: it builds (or receives) a ``PipelinePlan`` once per shape
  and hands execution to the executor the plan selects
  (``core.executors.get_executor``).

Given operand shapes, this module picks (a) the number of splits from the
analytic model in ``core.analytic`` and (b) Pallas block shapes for the
three pipeline stages, so callers never hand-tune kernel launches.

The analytic planner is the fallback and the seed of the search space;
the *measured* layer lives in ``core.autotune``: ``select_pipeline_plan``
consults a persistent ``PlanCache`` when given one (hit returns without
re-tuning) and can hand a miss to the measurement-driven autotuner
(``autotune=True``), which times candidate plans on the live backend.

Heuristics of the analytic layer (closed-form, shape-only):

* **num_splits** — the smallest ``s`` with ``s * BPS(k) >= mantissa_space``
  (Eq. 5 / Table 2): the paper's INT8xs operating point for a target
  mantissa-space length (70 bits for the DGEMM-replacement mode). Callers
  wanting data-dependent selection use ``core.auto_split`` instead; this
  planner is shape-only so it can run before the operands exist.
* **GEMM blocks (bm, bn, bk)** — largest power-of-two, MXU-aligned tiles
  whose working set ``bm*bk + bn*bk (int8) + 4*bm*bn (int32)`` fits the
  VMEM budget (default: half of 16 MiB, leaving room for double
  buffering). Under pressure the reduction slab ``bk`` halves first (it
  shrinks BOTH int8 operand tiles at once and only lengthens the inner
  k loop), then ``bm``, then ``bn`` down to their alignment floors.
* **split blocks** — the split kernel's output block is ``num_splits``
  times its input tile, so the input tile is sized from
  ``(num_splits + 8) * split_bm * split_bk <= budget`` (8 ~= two float32
  input blocks at 4 bytes each per int8 output element).
* **accum blocks** — elementwise kernel; the largest aligned tile for the
  (m, n) output with 4 arrays resident (p, c_hi, c_lo, + headroom).
* **schedule** — ``fuse_diagonals`` always (the int32 pre-accumulation is
  exact, strictly fewer high-precision accumulations);``concat_k`` when
  the per-GEMM reduction is short (k <= CONCAT_K_MAX) so that one big
  MXU launch amortizes what would otherwise be launch-bound slice GEMMs.

``apply_plan`` folds a plan back into an ``OzakiConfig`` without importing
it (plain ``dataclasses.replace``), keeping this module import-cycle-free.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Sequence

# alignment vocabulary is owned by the kernels' shared launch layer, so
# the planner's choices match shrink_block's exactly (repro.core imports
# repro.kernels.launch only — the kernels themselves import repro.core
# lazily, so there is no cycle).
from repro.kernels.launch import (GEMM_BLOCKS, LANE, SUBLANE_F32 as SUBLANE,
                                  SUBLANE_I8, VMEM_BYTES,
                                  align_up as _align_up, epilogue_blocks)

from .analytic import DGEMM_MANTISSA_SPACE, INT8_INT32, MMUSpec
from .splitting import slice_width
from .warn_once import WarnOnceLatch

VMEM_BUDGET = VMEM_BYTES // 2      # leave half for double buffering
CONCAT_K_MAX = 2048                 # below this, slice GEMMs are launch-bound

BACKENDS = ("xla", "pallas", "pallas_fused")
FUSION_MODES = ("none", "stages", "epilogue", "streaming")
BATCH_LAYOUTS = ("none", "rows", "grid")
# Which emulation algorithm a plan executes: Scheme I ("ozaki_fp64",
# slice-pair GEMMs — everything above) or Scheme II ("ozaki2_fp64",
# residue-system GEMMs + CRT — ``core.modular``). The scheme is part of
# the plan because the executor family, the GEMM count, and the accuracy
# bound all pivot on it; ``core.accuracy.resolve_accuracy`` arbitrates
# between the two per (shape, target).
PLAN_SCHEMES = ("ozaki_fp64", "ozaki2_fp64")
# What crosses the interconnect when the GEMM is sharded: "f64" moves
# f64 operand words (the GSPMD auto-sharding baseline gathers operands
# around the opaque kernels), "int8" ships the quantized Ozaki
# representation itself — packed int8 slice stacks + int32 exponent
# vectors for gathers, exact int32 pair partials for reductions
# (parallel.compression.SliceWire / parallel.ozaki_shard schedules).
# Result-invariant: every transport is bitwise-identical to the
# single-device reference (integer collectives are associative).
COMM_MODES = ("f64", "int8")
# Fast-mode pair truncation (see core.accuracy): "full" keeps the whole
# schedule; "diagonal" drops the last (least-significant) anti-diagonal
# group; "budget:N" keeps only the N highest-significance pairs. The
# policy is part of the plan, so executors thread it into the kernels'
# grid construction (fewer pair steps launched) — never a post-hoc mask.
PAIR_POLICIES = ("full", "diagonal", "budget:N")

# The batch-grid epilogue kernels ship with this PR; the env knob exists
# for deployments that need to fall back to the stage-fused pipeline on
# batched calls (e.g. a backend where the 5-D epilogue grid is not yet
# validated). The fallback warns once per reason instead of silently
# switching fusion mode. The latch is a shared ``WarnOnceLatch`` so the
# conftest-wide ``reset_all_warn_latches`` covers it.
BATCHED_EPILOGUE_ENV = "REPRO_OZAKI_BATCHED_EPILOGUE"
_DOWNGRADE_LATCH = WarnOnceLatch("fuse_epilogue_downgrade")


def batched_epilogue_enabled() -> bool:
    return os.environ.get(BATCHED_EPILOGUE_ENV, "1") != "0"


def _warn_downgrade_once(reason: str) -> None:
    _DOWNGRADE_LATCH.warn(
        reason, f"fuse_epilogue downgraded to fusion='stages': {reason}",
        stacklevel=4)


def reset_downgrade_warnings() -> None:
    """Reset the warn-once latch to fresh-process state.

    The latch is module-level state, so without a reset only the FIRST
    plan built after the env knob flips would warn — a second test (or a
    re-configured long-lived process) would see silence. Test fixtures
    (``tests/conftest.py``) reset every registered latch around every
    test (``core.warn_once.reset_all_warn_latches``); deployments that
    re-read the env knob at runtime should call this when they do.
    """
    _DOWNGRADE_LATCH.reset()


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Block shapes + schedule for one fused-pipeline launch (hashable).

    The GEMM blocks are requests, which a kernel's launch recipe
    (``kernels/launch.py``) only shrinks; ``None`` requests nothing and
    leaves the blocks to the recipe of the GEMM family that runs them:
    ``GEMM_BLOCKS`` (256 x 256 x 512) for the stage-fused, streaming and
    fused-CRT kernels, the VMEM model of ``epilogue_blocks`` for the
    epilogue-fused ones. The kernel wrappers' defaults are the same
    ``None``, so ``TilePlan()`` launches what a bare kernel call does.
    """

    bm: Optional[int] = None        # int8 GEMM output rows per block
    bn: Optional[int] = None        # int8 GEMM output cols per block
    bk: Optional[int] = None        # int8 GEMM reduction slab
    split_bm: int = 256             # split kernel input tile rows
    split_bk: int = 256             # split kernel input tile cols
    accum_bm: int = 256             # accumulation tile rows
    accum_bn: int = 256             # accumulation tile cols
    num_splits: int = 9
    fuse_diagonals: bool = True
    concat_k: bool = False


def _pow2_at_most(x: int, lo: int) -> int:
    """Largest power of two <= x, floored at ``lo``."""
    if x <= lo:
        return lo
    return 2 ** int(math.floor(math.log2(x)))


def select_num_splits(k: int, *, mantissa_space: int = DGEMM_MANTISSA_SPACE,
                      mmu: MMUSpec = INT8_INT32) -> int:
    """Paper operating point: ceil(mantissa_space / BPS(k))."""
    return mmu.num_splits(k, mantissa_space)


def select_plan(m: int, n: int, k: int, *, batch: int = 1,
                num_splits: int | None = None,
                mantissa_space: int = DGEMM_MANTISSA_SPACE,
                mmu: MMUSpec = INT8_INT32,
                vmem_budget: int = VMEM_BUDGET) -> TilePlan:
    """Pick block shapes and split count from operand shapes alone.

    ``batch`` scales nothing directly (the batch is a grid dimension, not
    a VMEM resident), but a multi-row batch disables ``concat_k`` — the
    concatenated operands would be materialized once per batch row.
    """
    if num_splits is None:
        num_splits = select_num_splits(k, mantissa_space=mantissa_space,
                                       mmu=mmu)

    # --- GEMM blocks: shrink from the GEMM family's GEMM_BLOCKS.
    # bm is an int8 A-tile sublane dim (32-aligned); bn doubles as the
    # int32 C-tile lane dim, so the stricter 128 alignment applies.
    cap_m, cap_n, cap_k = GEMM_BLOCKS
    bm = min(cap_m, _pow2_at_most(_align_up(m, SUBLANE_I8), SUBLANE_I8))
    bn = min(cap_n, _pow2_at_most(_align_up(n, LANE), LANE))
    bk = min(cap_k, _pow2_at_most(_align_up(k, LANE), LANE))
    while bm * bk + bn * bk + 4 * bm * bn > vmem_budget:
        if bk > LANE:
            bk //= 2
        elif bm > SUBLANE_I8:
            bm //= 2
        elif bn > LANE:
            bn //= 2
        else:
            break

    # --- split blocks: output is num_splits x the (int8) input tile.
    split_bm = min(256, _pow2_at_most(_align_up(m, SUBLANE_I8), SUBLANE_I8))
    split_bk = min(256, _pow2_at_most(_align_up(k, LANE), LANE))
    while (num_splits + 8) * split_bm * split_bk > vmem_budget and \
            split_bk > LANE:
        split_bk //= 2

    # --- accum blocks: 4 f32/int32 arrays resident per tile.
    accum_bm = min(256, _pow2_at_most(_align_up(m, SUBLANE), SUBLANE))
    accum_bn = min(256, _pow2_at_most(_align_up(n, LANE), LANE))
    while 16 * accum_bm * accum_bn > vmem_budget and accum_bn > LANE:
        accum_bn //= 2

    return TilePlan(bm=bm, bn=bn, bk=bk, split_bm=split_bm,
                    split_bk=split_bk, accum_bm=accum_bm, accum_bn=accum_bn,
                    num_splits=num_splits, fuse_diagonals=True,
                    concat_k=(k <= CONCAT_K_MAX and batch == 1))


def _epilogue_tile(tile: TilePlan, m: int, n: int, k: int, *, accum: str,
                  request: bool = False) -> TilePlan:
    """``tile`` with the GEMM blocks an epilogue-fused launch takes at
    this shape: ``epilogue_blocks`` for the accumulator planes ``accum``
    carries (two float32 for df32, one float64 for f64), capped by the
    tile's own blocks where they are a ``request``."""
    planes, el_bytes = (1, 8) if accum == "f64" else (2, 4)
    req = (tile.bm, tile.bn, tile.bk) if request else (None, None, None)
    bm, bn, bk = epilogue_blocks(m, n, k, *req, planes=planes,
                                 el_bytes=el_bytes)
    return dataclasses.replace(tile, bm=bm, bn=bn, bk=bk)


def apply_plan(cfg, plan: TilePlan):
    """Fold a TilePlan into an OzakiConfig (any dataclass with the fields)."""
    return dataclasses.replace(cfg, num_splits=plan.num_splits,
                               fuse_diagonals=plan.fuse_diagonals,
                               concat_k=plan.concat_k, tile=plan)


# ----------------------------------------------------------------------------
# Pipeline planning: the full execution strategy for one GEMM shape
# ----------------------------------------------------------------------------

def diagonal_groups(num_splits: int,
                    full_pairs: bool = False,
                    pair_budget: Optional[int] = None
                    ) -> Sequence[tuple[int, Sequence[tuple[int, int]]]]:
    """0-based (t, [(p, q)...]) anti-diagonal groups with t = p + q.

    The schedule vocabulary shared by ``OzakiConfig`` and ``PipelinePlan``:
    the paper computes pairs with i + j <= s + 1 (``t <= s - 1`` 0-based);
    ``full_pairs`` keeps all 2s - 1 diagonals. ``pair_budget`` (from
    ``parse_pair_policy``) keeps only the first N pairs in significance
    order — diagonals ascending, the last kept diagonal possibly partial
    (its pairs share one scale, so which prefix survives is
    accuracy-neutral within the diagonal).
    """
    s = num_splits
    t_max = 2 * s - 2 if full_pairs else s - 1
    out = []
    remaining = pair_budget
    for t in range(t_max + 1):
        pairs = [(p, t - p) for p in range(max(0, t - s + 1),
                                           min(s - 1, t) + 1)]
        if remaining is not None:
            if remaining <= 0:
                break
            pairs = pairs[:remaining]
            remaining -= len(pairs)
        out.append((t, pairs))
    return out


def parse_pair_policy(policy: str, num_splits: int,
                      full_pairs: bool = False) -> Optional[int]:
    """Pair budget (kept-pair count) encoded by a policy string.

    ``None`` means "no truncation" (the full schedule); budgets are
    clamped to ``[1, total]`` — a plan always computes at least the
    leading (0, 0) pair. Raises ``ValueError`` on malformed policies, so
    ``PipelinePlan.__post_init__`` can validate by parsing.
    """
    groups = diagonal_groups(num_splits, full_pairs)
    total = sum(len(p) for _, p in groups)
    if policy == "full":
        return None
    if policy == "diagonal":
        return max(1, total - len(groups[-1][1]))
    if policy.startswith("budget:"):
        try:
            n = int(policy[len("budget:"):])
        except ValueError:
            n = 0
        if n < 1:
            raise ValueError(f"pair budget must be a positive int, "
                             f"got {policy!r}")
        return min(n, total)
    raise ValueError(f"unknown pair_policy {policy!r}; expected one of "
                     f"{PAIR_POLICIES}")


def plan_schedule_ok(plan: "PipelinePlan", k: int, *, ell_acc: int = 31,
                     ell_in: int = 7) -> bool:
    """True when the plan's split schedule is executable on the df32 path.

    ``ozaki_matmul_dw`` requires ``(num_splits + 1) * w <= 120`` so every
    accumulation scale stays in f32 normal range; a candidate enumerated
    above that (e.g. ``search_num_splits`` widening s) would crash
    mid-measurement. f64 accumulation has no such ceiling.
    """
    if plan.accum != "df32":
        return True
    fuse_terms = (plan.num_splits
                  if (plan.fuse_diagonals or plan.concat_k) else 1)
    w = slice_width(k, ell_acc=ell_acc, ell_in=ell_in,
                    fuse_terms=fuse_terms)
    return (plan.num_splits + 1) * w <= 120


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """Execution strategy for one Ozaki GEMM shape (hashable, serializable).

    Built once per shape — by ``plan_for`` (reflecting an ``OzakiConfig``)
    or ``select_pipeline_plan`` (from shapes alone) — and consumed by
    ``core.executors``:

    num_splits:   s (INT8xs operating point).
    tile:         block shapes for every kernel launch (``TilePlan``; its
                  own num_splits/schedule fields are advisory — the plan's
                  top-level fields below are authoritative).
    backend:      "xla" | "pallas" | "pallas_fused" — executor family.
    fusion:       "none"      — every stage a separate op/kernel;
                  "stages"    — one-pass split + fused accumulation kernels
                                (the PR 1 ``pallas_fused`` pipeline);
                  "epilogue"  — GEMM and scaled accumulation in ONE kernel:
                                int32 group products never reach HBM;
                  "streaming" — split + GEMM + accumulation in ONE kernel:
                                the int8 slice stacks are extracted
                                tile-wise in VMEM and never reach HBM
                                either (only the operand words and the
                                carried C cross the HBM boundary).
    batch_layout: "none" — unbatched (m, k) x (k, n);
                  "rows" — broadcast weights, batch folded into rows;
                  "grid" — explicit batch grid dimension on every stage.
    shard_axis:   mesh axis name the k (reduction) dim is sharded over, or
                  None. Consumed by ``parallel.ozaki_shard`` composition
                  and the model/serving layers; the executors themselves
                  stay single-device (GSPMD inserts the collectives).
    comm:         "f64" — sharded calls move f64 operand words (GSPMD
                  baseline); "int8" — ship the packed int8-slice
                  representation / exact int32 partials instead
                  (``parallel.ozaki_shard`` explicit schedules;
                  ``comm_bytes_model`` prices both). Result-invariant:
                  a no-op without a shard axis + registered mesh.
    pair_policy:  "full" | "diagonal" | "budget:N" — fast-mode pair
                  truncation (``core.accuracy`` bounds the error). The
                  policy shapes ``diagonals()``, so every executor and
                  the Pallas pair-grid dimensions shrink with it.
    fuse_diagonals / concat_k / full_pairs / accum / interpret: the
    schedule and numeric knobs, verbatim from the config.

    scheme / beta / num_moduli: the emulation algorithm. Scheme I
    (``"ozaki_fp64"``) ignores beta/num_moduli (0 sentinels); Scheme II
    (``"ozaki2_fp64"``) records its operating point — ``beta`` mantissa
    bits (= ``num_splits * 7``, the integerization slice count) and the
    residue-GEMM count ``num_moduli`` (the moduli themselves re-derive
    deterministically as ``modular.usable_moduli(k)[:num_moduli]``).
    Scheme II constraints: f64 accumulation only (the CRT reconstruction
    is an FP64 sum), "full" pair policy (there is no pair schedule to
    truncate — accuracy scales via beta), and fusion "none"/"stages"/
    "epilogue" — "epilogue" is the fused-CRT kernel (balanced-Garner
    reconstruction in VMEM scratch over the modulus grid axis; the int32
    residue products never round-trip through HBM). There is no Scheme II
    streaming kernel.
    """

    num_splits: int = 9
    tile: TilePlan = TilePlan()
    backend: str = "xla"
    fusion: str = "none"
    batch_layout: str = "none"
    shard_axis: Optional[str] = None
    comm: str = "f64"
    pair_policy: str = "full"
    fuse_diagonals: bool = True
    concat_k: bool = False
    full_pairs: bool = False
    accum: str = "f64"
    interpret: Optional[bool] = None
    scheme: str = "ozaki_fp64"
    beta: int = 0
    num_moduli: int = 0

    def __post_init__(self):
        if self.interpret is None:
            from repro.kernels.launch import resolve_interpret
            object.__setattr__(self, "interpret", resolve_interpret(None))
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {BACKENDS}")
        if self.fusion not in FUSION_MODES:
            raise ValueError(f"unknown fusion {self.fusion!r}; "
                             f"expected one of {FUSION_MODES}")
        if self.batch_layout not in BATCH_LAYOUTS:
            raise ValueError(f"unknown batch_layout {self.batch_layout!r}; "
                             f"expected one of {BATCH_LAYOUTS}")
        if self.accum not in ("f64", "df32"):
            raise ValueError(f"unknown accum {self.accum!r}")
        if self.comm not in COMM_MODES:
            raise ValueError(f"unknown comm {self.comm!r}; "
                             f"expected one of {COMM_MODES}")
        if self.scheme not in PLAN_SCHEMES:
            raise ValueError(f"unknown scheme {self.scheme!r}; "
                             f"expected one of {PLAN_SCHEMES}")
        if self.scheme == "ozaki2_fp64":
            if self.beta < 1 or self.num_moduli < 1:
                raise ValueError(
                    f"ozaki2_fp64 plans need beta >= 1 and num_moduli >= 1, "
                    f"got beta={self.beta}, num_moduli={self.num_moduli}")
            if self.accum != "f64":
                raise ValueError("ozaki2_fp64 accumulates in f64 only "
                                 f"(CRT reconstruction), got {self.accum!r}")
            if self.fusion not in ("none", "stages", "epilogue"):
                raise ValueError(
                    f"ozaki2_fp64 supports fusion 'none'/'stages'/"
                    f"'epilogue' (fused-CRT reconstruction; no residue "
                    f"streaming kernel), got {self.fusion!r}")
            if self.pair_policy != "full":
                raise ValueError(
                    "ozaki2_fp64 has no pair schedule to truncate "
                    f"(accuracy scales via beta), got pair_policy="
                    f"{self.pair_policy!r}")
        parse_pair_policy(self.pair_policy, self.num_splits,
                          self.full_pairs)       # raises on malformed

    def diagonals(self):
        return diagonal_groups(
            self.num_splits, self.full_pairs,
            pair_budget=parse_pair_policy(self.pair_policy, self.num_splits,
                                          self.full_pairs))

    @property
    def num_gemms(self) -> int:
        if self.scheme == "ozaki2_fp64":
            return self.num_moduli          # one residue GEMM per modulus
        return sum(len(p) for _, p in self.diagonals())

    # --- serialization (deployment caches / cross-process handoff) -----
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "PipelinePlan":
        d = dict(d)
        tile = d.get("tile")
        if isinstance(tile, dict):
            d["tile"] = TilePlan(**tile)
        return cls(**d)


def _fusion_for(backend: str, fuse_epilogue: bool, batch_layout: str,
                streaming: bool = False) -> str:
    if backend != "pallas_fused":
        return "none"
    if streaming:
        if batch_layout == "grid" and not batched_epilogue_enabled():
            # streaming reuses the batch-grid epilogue machinery, so the
            # same env knob gates it (and the same warn-once fires).
            _warn_downgrade_once(
                f"stacked-weights batch with {BATCHED_EPILOGUE_ENV}=0 — "
                "the batch-grid streaming kernel is disabled, falling "
                "back to the stage-fused pipeline (batched GEMM + fused "
                "accumulation)")
            return "stages"
        return "streaming"
    if not fuse_epilogue:
        return "stages"
    if batch_layout == "grid" and not batched_epilogue_enabled():
        _warn_downgrade_once(
            f"stacked-weights batch with {BATCHED_EPILOGUE_ENV}=0 — the "
            "batch-grid epilogue kernel is disabled, falling back to the "
            "stage-fused pipeline (batched GEMM + fused accumulation)")
        return "stages"
    return "epilogue"


def plan_for(cfg, *, batch_layout: str = "none") -> PipelinePlan:
    """Reflect an ``OzakiConfig`` (duck-typed) into a ``PipelinePlan``.

    ``cfg.tile=None`` requests no GEMM blocks (``TilePlan()``), so each
    kernel family's launch recipe sizes its own; schedule flags come
    from the config, never from the tile.
    """
    tile = cfg.tile if cfg.tile is not None else TilePlan(
        num_splits=cfg.num_splits, fuse_diagonals=cfg.fuse_diagonals,
        concat_k=cfg.concat_k)
    return PipelinePlan(
        num_splits=cfg.num_splits, tile=tile, backend=cfg.backend,
        fusion=_fusion_for(cfg.backend, getattr(cfg, "fuse_epilogue", False),
                           batch_layout,
                           streaming=getattr(cfg, "streaming", False)),
        batch_layout=batch_layout,
        shard_axis=getattr(cfg, "shard_axis", None),
        comm=getattr(cfg, "comm", "f64"),
        pair_policy=getattr(cfg, "pair_policy", "full"),
        fuse_diagonals=cfg.fuse_diagonals, concat_k=cfg.concat_k,
        full_pairs=cfg.full_pairs, accum=cfg.accum, interpret=cfg.interpret)


def _cached_hit_acceptable(hit: PipelinePlan, k: int, *, num_splits,
                           target_error, accuracy_pinned: bool,
                           policy: str, scheme: str = "ozaki_fp64",
                           num_moduli=None) -> bool:
    """Shared cache-hit validation for ``select_pipeline_plan`` and
    ``autotune_plan`` (see the comment at the call site).

    Under a pinned ``target_error`` the TARGET is the contract, so a hit
    from EITHER scheme family is accepted when its guaranteed bound
    meets it — a measured cross-scheme winner must not force eternal
    re-tuning. Without a target the requested scheme must match exactly
    (and Scheme II hits must match the resolved modulus count, the
    result-affecting knob of that family).
    """
    hit_scheme = getattr(hit, "scheme", "ozaki_fp64")
    if target_error is not None:
        from .accuracy import plan_meets_target      # lazy: no cycle
        return plan_meets_target(hit, k, target_error)
    if scheme == "ozaki2_fp64":
        return hit_scheme == "ozaki2_fp64" and \
            (num_moduli is None or hit.num_moduli == num_moduli)
    if hit_scheme != "ozaki_fp64":
        return False
    if accuracy_pinned:
        return hit.num_splits == num_splits and hit.pair_policy == policy
    return (num_splits is None or hit.num_splits == num_splits) and \
        hit.pair_policy == "full"


def select_pipeline_plan(m: int, n: int, k: int, *, batch: int = 1,
                         broadcast_weights: bool = False,
                         backend: str = "pallas_fused", accum: str = "df32",
                         num_splits: int | None = None,
                         fuse_epilogue: bool = True,
                         streaming: bool = False,
                         shard_axis: Optional[str] = None,
                         comm: str = "f64",
                         interpret: Optional[bool] = None,
                         target_error: Optional[float] = None,
                         fast_mode: bool = False,
                         pair_policy: Optional[str] = None,
                         mantissa_space: int = DGEMM_MANTISSA_SPACE,
                         mmu: MMUSpec = INT8_INT32,
                         vmem_budget: Optional[int] = None,
                         cache=None, autotune: bool = False,
                         dtype: Optional[str] = None,
                         device_kind: Optional[str] = None,
                         scheme: str = "ozaki_fp64",
                         num_moduli: Optional[int] = None) -> PipelinePlan:
    """Build the full execution strategy from shapes alone.

    ``batch``/``broadcast_weights`` describe the batched API's operands:
    broadcast weights fold the batch into rows (tiles are sized for the
    folded ``batch * m`` row extent — one big GEMM), a stacked-weights
    batch becomes an explicit grid dimension (and disables ``concat_k``,
    whose concatenated operands would be materialized per batch row).

    ``target_error`` / ``fast_mode`` / ``pair_policy`` pin an accuracy
    operating point (``core.accuracy.resolve_accuracy``): the target can
    REDUCE the split count below the ``mantissa_space`` default, fast
    mode truncates slice pairs to the minimal budget meeting the target
    (or drops the last diagonal when no target is set). When any of the
    three is given, a cached plan must match the resolved
    ``(num_splits, pair_policy)`` to be accepted — both are
    result-affecting.

    ``cache`` (a ``core.autotune.PlanCache``) short-circuits planning: a
    hit for ``(m, n, k, batch, dtype, backend, device_kind)`` returns
    the cached plan without re-tuning. On a miss the analytic plan above
    is returned — unless ``autotune=True``, in which case the measured
    autotuner (``core.autotune.autotune_plan``) times the candidate
    plans on the live backend, stores the winner in the cache, and
    returns it. ``dtype`` defaults from ``accum`` ("f64" -> float64,
    else float32 — the operand dtype the pipeline runs on).

    ``scheme="ozaki2_fp64"`` plans the residue-system path instead:
    ``target_error`` / ``num_moduli`` resolve the Scheme II operating
    point (``core.modular.resolve_modular``), the plan cache is keyed
    with the scheme, and fast-mode/pair-policy knobs are rejected (the
    residue path has no pair schedule).

    GEMM blocks: ``select_plan``'s, except that an epilogue-fused plan
    takes the epilogue family's own (``_epilogue_tile``). A
    ``vmem_budget`` given here is a request on every family: it sizes
    ``select_plan``'s blocks (``VMEM_BUDGET`` when None), and they then
    cap the epilogue blocks too.
    """
    from repro.kernels.launch import resolve_interpret
    interpret = resolve_interpret(interpret)
    budget = VMEM_BUDGET if vmem_budget is None else vmem_budget
    if batch <= 1 and not broadcast_weights:
        layout = "none"
    elif broadcast_weights:
        layout = "rows"
    else:
        layout = "grid"
    if scheme not in PLAN_SCHEMES:
        raise ValueError(f"unknown scheme {scheme!r}; "
                         f"expected one of {PLAN_SCHEMES}")
    if scheme == "ozaki2_fp64":
        if fast_mode or pair_policy is not None:
            raise ValueError(
                "ozaki2_fp64 has no pair schedule: fast_mode/pair_policy "
                "do not apply (set target_error or num_moduli instead)")
        if streaming:
            raise ValueError(
                "ozaki2_fp64 has no streaming kernel: the residue stacks "
                "are built by residues_from_slices (set fuse_epilogue for "
                "the fused-CRT reconstruction instead)")
        # lazy: core.modular imports this module at top
        from .modular import modular_plan, resolve_modular
        point = resolve_modular(k, target_error=target_error,
                                num_moduli=num_moduli,
                                mantissa_space=mantissa_space)
        if cache is not None or autotune:
            from .autotune import (autotune_plan, plan_cache_key,
                                   warn_if_interpret_ranked)
            key = plan_cache_key(m, n, k, batch=batch, dtype=dtype,
                                 accum="f64", backend=backend,
                                 device_kind=device_kind,
                                 scheme="ozaki2_fp64")
            if cache is not None:
                hit = cache.get(key)
                if hit is not None and _cached_hit_acceptable(
                        hit, k, num_splits=None, target_error=target_error,
                        accuracy_pinned=target_error is not None,
                        policy="full", scheme="ozaki2_fp64",
                        num_moduli=len(point.moduli)):
                    warn_if_interpret_ranked(cache, key, interpret)
                    return hit
            if autotune:
                return autotune_plan(
                    m, n, k, batch=batch,
                    broadcast_weights=broadcast_weights, backend=backend,
                    accum="f64", interpret=interpret,
                    target_error=target_error, dtype=dtype,
                    device_kind=device_kind, mantissa_space=mantissa_space,
                    mmu=mmu, vmem_budget=vmem_budget, cache=cache,
                    scheme="ozaki2_fp64",
                    num_moduli=len(point.moduli)).best
        m_eff = m * batch if layout == "rows" else m
        tile = select_plan(m_eff, n, k,
                           batch=batch if layout == "grid" else 1,
                           num_splits=point.num_splits,
                           mantissa_space=mantissa_space, mmu=mmu,
                           vmem_budget=budget)
        return modular_plan(k, point=point, backend=backend,
                            fuse_epilogue=(fuse_epilogue and
                                           backend == "pallas_fused"),
                            interpret=interpret, tile=tile,
                            batch_layout=layout)
    accuracy_pinned = (target_error is not None or fast_mode or
                      pair_policy is not None)
    policy = pair_policy if pair_policy is not None else "full"
    if accuracy_pinned:
        from .accuracy import resolve_accuracy            # lazy: no cycle
        base_s = (num_splits if num_splits is not None else
                  select_num_splits(k, mantissa_space=mantissa_space,
                                    mmu=mmu))
        num_splits, policy = resolve_accuracy(
            k, base_s, target_error=target_error, fast_mode=fast_mode,
            pair_policy=policy)
    if cache is not None or autotune:
        from .autotune import (autotune_plan, plan_cache_key,   # lazy: no cycle
                               warn_if_interpret_ranked)
        key = plan_cache_key(m, n, k, batch=batch, dtype=dtype, accum=accum,
                             backend=backend, device_kind=device_kind)
        if cache is not None:
            hit = cache.get(key)
            # The key is deliberately coarser than the accuracy operating
            # point, so the hit path validates it:
            #  * target_error pinned — the TARGET is the contract: any
            #    cached point whose guaranteed bound meets it is accepted
            #    (a measured winner with more pairs/splits than the
            #    minimal resolution must not force eternal re-tuning);
            #  * fast_mode / explicit pair_policy without a target — the
            #    resolved (s, policy) point must match exactly;
            #  * no accuracy knobs — an explicit num_splits must match
            #    (PR 3 rule), and a fast-mode-truncated cached plan must
            #    NEVER be served silently: full schedules only.
            if hit is not None and _cached_hit_acceptable(
                    hit, k, num_splits=num_splits,
                    target_error=target_error,
                    accuracy_pinned=accuracy_pinned, policy=policy):
                warn_if_interpret_ranked(cache, key, interpret)
                return hit
        if autotune:
            return autotune_plan(
                m, n, k, batch=batch, broadcast_weights=broadcast_weights,
                backend=backend, accum=accum, num_splits=num_splits,
                fuse_epilogue=fuse_epilogue, streaming=streaming,
                shard_axis=shard_axis, comm=comm,
                interpret=interpret, target_error=target_error,
                pair_policy=policy if accuracy_pinned else None,
                dtype=dtype, device_kind=device_kind,
                mantissa_space=mantissa_space, mmu=mmu,
                vmem_budget=vmem_budget, cache=cache).best
    m_eff = m * batch if layout == "rows" else m
    tile = select_plan(m_eff, n, k, batch=batch if layout == "grid" else 1,
                       num_splits=num_splits, mantissa_space=mantissa_space,
                       mmu=mmu, vmem_budget=budget)
    fusion = _fusion_for(backend, fuse_epilogue, layout, streaming=streaming)
    if fusion == "epilogue":
        tile = _epilogue_tile(tile, m_eff, n, k, accum=accum,
                             request=vmem_budget is not None)
    return PipelinePlan(
        num_splits=tile.num_splits, tile=tile, backend=backend,
        fusion=fusion,
        batch_layout=layout, shard_axis=shard_axis, comm=comm,
        pair_policy=policy,
        fuse_diagonals=tile.fuse_diagonals, concat_k=tile.concat_k,
        accum=accum, interpret=interpret)


def apply_pipeline_plan(cfg, plan: PipelinePlan):
    """Fold a PipelinePlan back into an OzakiConfig-shaped dataclass."""
    return dataclasses.replace(
        cfg, num_splits=plan.num_splits, backend=plan.backend,
        fuse_diagonals=plan.fuse_diagonals, concat_k=plan.concat_k,
        full_pairs=plan.full_pairs, accum=plan.accum, tile=plan.tile,
        fuse_epilogue=(plan.fusion == "epilogue"),
        streaming=(plan.fusion == "streaming"),
        pair_policy=plan.pair_policy,
        shard_axis=plan.shard_axis, comm=plan.comm,
        interpret=plan.interpret)


def hbm_pass_model(num_splits: int, *, fused: bool = False,
                   fuse_diagonals: bool = True,
                   fuse_epilogue: bool = False,
                   fusion: Optional[str] = None,
                   batch: int = 1, batch_layout: str = "none",
                   pair_policy: str = "full",
                   scheme: str = "ozaki_fp64",
                   num_moduli: int = 0) -> dict:
    """Modeled HBM round-trips per stage for one operand/output matrix.

    Counts *array passes* (each read or write of a full matrix-sized
    buffer), the quantity the paper's Fig. 9 shows dominating the split
    and accumulation stages:

    * split — Algorithm 4 re-reads the residual every iteration
      (``s`` passes) while the one-pass kernel reads the input once.
      Streaming mode has no standalone split pass; instead each group's
      kernel re-reads the operand words (``groups`` input passes).
    * slices — every non-streaming mode materializes the (s, m, k) int8
      slice stack between split and GEMM: ``s`` write passes at the end
      of split plus one read pass per kept slice pair in the GEMM stage.
      Streaming extracts slices tile-wise in VMEM, so this item is 0 —
      the O(s·m·k) traffic the mode exists to remove (and which this
      model previously omitted entirely, hiding the win).
    * accum — the unfused path materializes the int32->float conversion
      and the scaled term before the compensated add (2 extra passes per
      accumulation group); the stage-fused kernel does conversion + scale
      + add in registers within one VMEM pass but still reads the int32
      group product the GEMM materialized; the epilogue-fused and
      streaming GEMMs accumulate inside the GEMM grid so the int32
      product never round-trips at all — only the carried C read/write
      remains.

    Per-operand passes at s=9, full schedule (45 pairs, 9 groups):

    ====================  =====  ======  =====  =====
    fusion                split  slices  accum  total
    ====================  =====  ======  =====  =====
    "none"                    9      54     45    108
    "stages"                  1      54     27     82
    "epilogue"                1      54     18     73
    "streaming"               9       0     18     27
    ====================  =====  ======  =====  =====

    Streaming is strictly below epilogue for every schedule: the saved
    slice traffic ``s + kept`` always exceeds the extra operand re-reads
    ``groups - 1`` (``kept >= groups``).

    ``fusion`` names the plan's mode directly (``PipelinePlan.fusion``)
    and overrides the legacy ``fused``/``fuse_epilogue`` flags, which
    remain for callers modeling the pre-streaming pipelines.

    ``batch``/``batch_layout`` model the batched pipeline: every layout
    runs the identical per-element pipeline (the "rows" layout folds the
    batch into rows of ONE matrix; the "grid" layout walks the same
    blocks per batch row, including the batch-grid epilogue kernel), so
    passes scale linearly with the batch size. Until the batch-grid
    epilogue kernel existed, a "grid" batch downgraded epilogue plans to
    the stage-fused pipeline — that legacy state is modeled by calling
    with ``fuse_epilogue=False`` — so the kernel removes one modeled
    pass per accumulation group (3 -> 2) on the batched path.

    ``scheme="ozaki2_fp64"`` prices the residue-system pipeline instead
    (``num_moduli`` = ``ell``, the CRT modulus count). Its stages:

    * split — identical to Scheme I (s residual passes unfused, one
      input read for the one-pass kernel).
    * slices — the (s, m, k) int8 stack is written once by split and
      read ONCE by the residue extraction (``residues_from_slices``
      contracts the whole slice axis per modulus in one tensordot pass),
      so ``2 * s`` — not the per-pair re-reads Scheme I pays.
    * residues — the (ell, m, k) int8 residue stacks: ell write passes
      by the extraction plus ell read passes by the batched GEMM. This
      is the line item the model previously had no vocabulary for
      (mirroring the slice-stack fix: Scheme I plans carry
      ``residues = 0``).
    * accum — unfused/stage-fused: the (ell, m, n) int32 residue
      products round-trip through HBM between the GEMM and the Garner
      reconstruction (``2 * ell``) plus the f64 output write; the
      fused-CRT epilogue (``fusion="epilogue"``) reconstructs in VMEM
      scratch over the modulus grid axis, so only the output write
      remains — strictly ``2 * ell`` passes fewer.

    Scheme II at s=9, ell=15: "none" 9+18+30+31=88, "stages"
    1+18+30+31=80, "epilogue" 1+18+30+1=50. There is no Scheme II
    streaming mode.
    """
    if batch_layout not in BATCH_LAYOUTS:
        raise ValueError(f"unknown batch_layout {batch_layout!r}; "
                         f"expected one of {BATCH_LAYOUTS}")
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if batch > 1 and batch_layout == "none":
        raise ValueError("batch > 1 requires batch_layout 'rows' or 'grid'")
    if fusion is not None:
        if fusion not in FUSION_MODES:
            raise ValueError(f"unknown fusion {fusion!r}; "
                             f"expected one of {FUSION_MODES}")
        fused = fusion in ("stages", "epilogue", "streaming")
        fuse_epilogue = fusion == "epilogue"
    streaming = fusion == "streaming"
    fused = fused or fuse_epilogue      # epilogue fusion implies fused
    s = num_splits
    if scheme not in PLAN_SCHEMES:
        raise ValueError(f"unknown scheme {scheme!r}; "
                         f"expected one of {PLAN_SCHEMES}")
    if scheme == "ozaki2_fp64":
        if num_moduli < 1:
            raise ValueError("ozaki2_fp64 pass model needs num_moduli >= 1 "
                             f"(the CRT modulus count), got {num_moduli}")
        if streaming:
            raise ValueError("ozaki2_fp64 has no streaming kernel")
        if pair_policy != "full":
            raise ValueError("ozaki2_fp64 has no pair schedule to "
                             f"truncate, got pair_policy={pair_policy!r}")
        ell = num_moduli
        split_passes = (1 if fused else s) * batch
        slices_passes = 2 * s * batch       # stack written s + read s
        residues_passes = 2 * ell * batch   # planes written ell + read ell
        accum_passes = (1 if fuse_epilogue else 2 * ell + 1) * batch
        return {"split": split_passes, "slices": slices_passes,
                "residues": residues_passes, "accum": accum_passes,
                "total": split_passes + slices_passes + residues_passes +
                accum_passes}
    # pair truncation drops whole accumulation groups (fuse_diagonals)
    # or individual pair products (paper-faithful schedule)
    gl = diagonal_groups(s, False,
                         pair_budget=parse_pair_policy(pair_policy, s))
    kept = sum(len(p) for _, p in gl)
    groups = len(gl) if fuse_diagonals else kept
    if streaming:
        # one operand-word read per group kernel; no slice stack at all
        split_passes = groups
        slices_passes = 0
        accum_passes = groups * 2        # read C + write C, nothing else
    else:
        split_passes = 1 if fused else s
        # the materialized (s, m, k) stack: written once by split, then
        # one slice plane read per kept pair by the GEMM stage
        slices_passes = s + kept
        if fuse_epilogue:
            accum_passes = groups * 2    # read C + write C, nothing else
        else:
            # per group: read P + read/write C(hi,lo); unfused adds
            # temp traffic
            accum_passes = groups * (3 if fused else 5)
    split_passes *= batch
    slices_passes *= batch
    accum_passes *= batch
    return {"split": split_passes, "slices": slices_passes,
            "residues": 0, "accum": accum_passes,
            "total": split_passes + slices_passes + accum_passes}


def comm_bytes_model(m: int, n: int, k: int, *, num_splits: int,
                     world: int, layout: str = "kshard",
                     comm: str = "f64", schedule: str = "psum",
                     batch: int = 1, fuse_diagonals: bool = True,
                     full_pairs: bool = False,
                     pair_policy: str = "full",
                     scheme: str = "ozaki_fp64",
                     num_moduli: int = 0) -> dict:
    """Modeled per-device interconnect bytes for one sharded GEMM — the
    ``hbm_pass_model`` companion for the transport layer.

    Counts the bytes ONE device sends over the links (ring-schedule
    accounting: an all-gather/reduce-scatter of a V-byte global buffer
    moves ``(P-1)/P * V`` bytes per device; an all-reduce moves twice
    that — reduce-scatter + all-gather). ``batch`` scales the
    activation-side items linearly (broadcast weights cross once).

    Layouts and what each transport moves:

    * ``layout="kshard"`` — the reduction dim is sharded.

      - ``comm="f64"`` (the GSPMD auto-sharding baseline): the Pallas
        kernel calls are opaque to the SPMD partitioner, so the jitted
        pipeline all-gathers BOTH f64 operands before computing —
        ``(P-1)/P * 8 * (m*k + k*n)`` bytes. This is exactly what
        ``ozaki_matmul_kshard_auto`` pays today.
      - ``comm="int8"``: slices stay device-local (each device splits
        only its k-chunk); what crosses the mesh is the exact int32
        anti-diagonal partials (4 bytes x ``groups`` x ``m*n``) plus
        two int32 exponent pmaxes. ``schedule="psum"``/``"overlap"``
        all-reduce the partials (2x factor); ``"reduce_scatter"``/
        ``"rs_stream"`` halve that by leaving C column-sharded.

    * ``layout="mnshard"`` — A row-sharded, B column-sharded; full k
      local. B's representation is all-gathered so every device can
      compute its row block against all columns:

      - ``comm="f64"``: gather B operand words, ``(P-1)/P * 8 * k*n``.
      - ``comm="int8"``: gather the packed ``SliceWire`` — int8 slice
        stack + int32 exponents, ``(P-1)/P * (s * k*n + 4*n)``.

      The model is honest about where int8 loses: the slice stack costs
      ``s`` bytes per element vs f64's 8, so the m/n-shard gather only
      wins for ``s < 8`` (e.g. ``target_error``-reduced split counts) —
      the headline >= 6x win is the k-shard layout's, where the int8
      path moves NO operand words at all and tall-k shapes amortize the
      ``m*n`` partials against the ``(m + n) * k`` operand gather.

    ``scheme="ozaki2_fp64"`` prices the residue-system transport instead
    (``num_moduli`` = ``ell``). k-shard int8 ships the exact int32
    residue partial stack — ``ell`` planes of ``4 * m*n`` bytes (the
    per-modulus products are exact int32 sums over the sharded k axis,
    so the reduction commutes with the CRT reconstruction) — plus the
    same two exponent pmaxes. m/n-shard int8 gathers the packed
    ``ResidueWire`` (int8 residue stack + exponents): ``ell`` bytes per
    element of B vs f64's 8, so the gather wins exactly when
    ``ell < 8`` — the same honesty note as Scheme I's ``s < 8``.

    Returns per-item bytes: ``operands`` (f64 words), ``slices`` (int8
    stacks — slice planes for Scheme I, residue planes for Scheme II),
    ``exponents`` (int32 vectors), ``partials`` (int32 group / residue
    products), and ``total``.
    """
    if layout not in ("kshard", "mnshard"):
        raise ValueError(f"unknown layout {layout!r}; expected 'kshard' "
                         f"or 'mnshard'")
    if comm not in COMM_MODES:
        raise ValueError(f"unknown comm {comm!r}; expected one of "
                         f"{COMM_MODES}")
    if schedule not in ("psum", "overlap", "reduce_scatter", "rs_stream",
                        "allgather"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    if scheme not in PLAN_SCHEMES:
        raise ValueError(f"unknown scheme {scheme!r}; "
                         f"expected one of {PLAN_SCHEMES}")
    ring = (world - 1) / world           # per-device ring fraction
    s = num_splits
    if scheme == "ozaki2_fp64":
        if num_moduli < 1:
            raise ValueError("ozaki2_fp64 comm model needs num_moduli >= 1 "
                             f"(the CRT modulus count), got {num_moduli}")
        ell = num_moduli
        operands = slices = exponents = partials = 0.0
        if layout == "kshard":
            if comm == "f64":
                operands = ring * 8 * (batch * m * k + k * n)
            else:
                exponents = 2 * ring * 4 * (batch * m + n)
                # exact int32 residue partials: one (m, n) plane per
                # modulus; all-reduce costs 2x a reduce-scatter
                factor = 2 if schedule in ("psum", "overlap") else 1
                partials = factor * ring * 4 * ell * batch * m * n
        else:                            # mnshard: gather B's residues
            if comm == "f64":
                operands = ring * 8 * k * n
            else:
                slices = ring * ell * k * n      # packed ResidueWire
                exponents = ring * 4 * n
        total = operands + slices + exponents + partials
        return {"operands": operands, "slices": slices,
                "exponents": exponents, "partials": partials,
                "total": total}
    gl = diagonal_groups(s, full_pairs,
                         pair_budget=parse_pair_policy(pair_policy, s,
                                                       full_pairs))
    groups = len(gl) if fuse_diagonals else sum(len(p) for _, p in gl)
    operands = slices = exponents = partials = 0.0
    if layout == "kshard":
        if comm == "f64":
            # GSPMD gathers both operands around the opaque kernels
            operands = ring * 8 * (batch * m * k + k * n)
        else:
            # int32 exponent pmax (all-reduce) over both row vectors
            exponents = 2 * ring * 4 * (batch * m + n)
            # exact int32 anti-diagonal partials; all-reduce costs 2x a
            # reduce-scatter (reduce-scatter + all-gather phases)
            factor = 2 if schedule in ("psum", "overlap") else 1
            partials = factor * ring * 4 * groups * batch * m * n
    else:                                # mnshard: gather B's columns
        if comm == "f64":
            operands = ring * 8 * k * n
        else:
            slices = ring * s * k * n
            exponents = ring * 4 * n
    total = operands + slices + exponents + partials
    return {"operands": operands, "slices": slices,
            "exponents": exponents, "partials": partials, "total": total}
