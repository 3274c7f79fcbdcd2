"""Ozaki-scheme GEMM on integer matrix units — Algorithm 3 of the paper.

``ozaki_matmul`` computes an FP64-accurate ``C = A @ B`` using only int8
matrix multiplications with int32 accumulation (the TPU MXU int8 path) plus
a high-precision scaled accumulation of the slice products.

This module is the thin *driver* of a planner/executor architecture:

  * ``core.tuning.PipelinePlan`` — the execution strategy for one shape
    (tiles, split count, fusion mode, batch layout, shard axis), built
    once per shape by ``plan_for`` (reflecting the ``OzakiConfig``) or
    ``select_pipeline_plan`` (from shapes alone).
  * ``core.executors`` — one executor class per strategy; the driver
    normalizes operands (transpose, batch folding), computes the deferred
    exponent base, and hands the three-stage pipeline (split, slice
    GEMMs, accumulate) to ``get_executor(plan)``.

Backends (``OzakiConfig.backend`` — executor families):

  * ``xla``          — every stage as composite XLA ops. The reference.
  * ``pallas``       — the int8 GEMMs run on the Pallas MXU kernel; split
    and accumulation stay XLA ops.
  * ``pallas_fused`` — the deployment path. With ``fuse_epilogue=False``
    (fusion mode "stages"): one-pass SplitInt kernel, Pallas MXU GEMMs,
    fused scaled-accumulation kernels. With ``fuse_epilogue=True``
    (fusion mode "epilogue"): GEMM and accumulation run in ONE kernel per
    anti-diagonal group — the int32 slice products accumulate in a VMEM
    scratch block and never round-trip to HBM (the remaining accumulation
    traffic ``core.tuning.hbm_pass_model`` charges the "stages" mode).
    Both modes are bitwise identical to ``xla`` for both accumulation
    modes (the kernels run the same rounding sequences).

Accumulation modes:
  * ``accum="f64"``  — the paper's mode (CPU validation; x64 required).
  * ``accum="df32"`` — double-float32 accumulation, deployable on TPU
    (no FP64 hardware exists there); carries 48 mantissa bits.

Scheduling modes (see DESIGN.md §4):
  * paper-faithful: each slice pair (i, j) with i + j <= s + 1 is a
    separate int8 GEMM followed by a scaled high-precision accumulation.
  * ``fuse_diagonals`` (O1): pairs on an anti-diagonal share their scale,
    so their int32 products are summed exactly in int32 first. Requires
    slack bits in alpha (``compute_alpha(..., fuse_terms=...)``).
  * ``concat_k`` (O2): realizes each anti-diagonal sum as ONE int8 GEMM
    over a k-concatenated operand pair (the epilogue-fused executor gets
    the same exact sum from its pair grid dimension instead).

Batched entry point: ``ozaki_matmul_batched`` handles ``(B, m, k) @
(B, k, n)`` stacks and the serving case ``(B, m, k) @ (k, n)`` (broadcast
weights). Broadcast weights collapse the batch into rows — one big GEMM,
bitwise identical to a Python loop over ``ozaki_matmul``. Fully-batched
operands run the SAME pipeline with an explicit batch dimension: the
split stage folds the stack into rows (row-independent, exact), the
GEMMs run the explicit batch-grid kernel (one launch per group, batch
outermost in the grid — no vmap), and the accumulation broadcasts the
per-(batch, row, col) exponent base. Gradients are defined via
``jax.custom_jvp`` with the exact-product rule ``dC = dA·B + A·dB``.

Sharding: ``OzakiConfig.shard_axis`` names a mesh axis the k (reduction)
dimension is sharded over; ``parallel.ozaki_shard`` composes the batched
API with that axis (the plan carries it; GSPMD inserts the collectives).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .executors import StreamingSplit, get_executor, int32_to_dw
from .splitting import SplitResult, slice_width
from .tuning import (BACKENDS, PipelinePlan, TilePlan, diagonal_groups,
                     parse_pair_policy, plan_for)
from .xmath import DW, dw_to_single, refuse_f64_on_tpu

@dataclasses.dataclass(frozen=True)
class OzakiConfig:
    """Configuration for one Ozaki GEMM.

    num_splits: s in the paper (INT8x{s}).
    accum: "f64" | "df32".
    backend: "xla" (lax ops) | "pallas" (MXU GEMM kernel only) |
        "pallas_fused" (fused split/GEMM/accumulate kernel pipeline).
    fuse_epilogue: with ``backend="pallas_fused"``, run GEMM + scaled
        accumulation in one kernel per group (int32 products stay in
        VMEM). Ignored by other backends. Stacked-weights batches run
        the batch-grid epilogue kernel (set the
        ``REPRO_OZAKI_BATCHED_EPILOGUE=0`` env knob to fall back to the
        stage-fused pipeline on batched calls; the fallback warns once).
    streaming: with ``backend="pallas_fused"``, fuse the SPLIT into the
        GEMM grid as well (``fusion="streaming"``): each (k-panel, pair)
        grid step extracts the int8 slices of its operand tiles in VMEM,
        so the slice stacks never materialize in HBM (see
        ``tuning.hbm_pass_model``'s "slices" item). Wins over
        ``fuse_epilogue`` when both are set; ignored by other backends;
        gated by the same env knob as the epilogue kernels on stacked
        batches.
    fuse_diagonals: O1 — exact int32 pre-accumulation per anti-diagonal.
    concat_k: O2 — one GEMM per anti-diagonal via k-concatenation.
    full_pairs: compute all s*s pairs (paper computes i+j <= s+1 only).
    pair_policy: "full" | "diagonal" | "budget:N" — fast-mode pair
        truncation: compute only the highest-significance slice pairs
        (``core.accuracy`` bounds the error; the truncated pair list is
        threaded into the executors' grids, never applied as a mask).
    target_error: accuracy target on the scaled error
        ``max |C - C_hat| / 2^{ea+eb}`` (see ``core.accuracy``). When
        set, the driver REDUCES num_splits to the smallest count whose
        guaranteed bound meets it (never raises it), per GEMM shape.
    fast_mode: truncate slice pairs to the minimal budget meeting
        ``target_error`` (or drop the last anti-diagonal when no target
        is set). An explicit non-"full" ``pair_policy`` wins over it.
    shard_axis: mesh axis name to shard the reduction (k) dim over, or
        None. Consumed by ``parallel.ozaki_shard`` / the serving layer.
    comm: "f64" (GSPMD moves f64 operand words around the sharded GEMM)
        | "int8" (ship the packed int8-slice representation / exact
        int32 partials instead — ``parallel.ozaki_shard`` explicit
        collective schedules). Result-invariant; ignored unless a shard
        axis and mesh are in play.
    ell_acc / ell_in: accumulator / input mantissa widths (Table 2).
    interpret: run Pallas kernels in interpret mode; None takes the host
        default (``kernels.ops.INTERPRET``: on off a TPU, off on one),
        resolved when the plan is built.
    tile: optional TilePlan with per-stage block shapes (core.tuning).
    """

    num_splits: int = 9
    accum: str = "f64"
    backend: str = "xla"
    fuse_epilogue: bool = False
    streaming: bool = False
    fuse_diagonals: bool = True
    concat_k: bool = False
    full_pairs: bool = False
    pair_policy: str = "full"
    target_error: Optional[float] = None
    fast_mode: bool = False
    shard_axis: Optional[str] = None
    comm: str = "f64"
    ell_acc: int = 31
    ell_in: int = 7
    interpret: Optional[bool] = None
    tile: Optional[TilePlan] = None

    def width_for(self, k: int) -> int:
        fuse_terms = self.max_fuse_terms if (self.fuse_diagonals or
                                             self.concat_k) else 1
        return slice_width(k, ell_acc=self.ell_acc, ell_in=self.ell_in,
                           fuse_terms=fuse_terms)

    @property
    def max_fuse_terms(self) -> int:
        # longest anti-diagonal: i+j = s+1 has s pairs (full: s as well)
        return self.num_splits

    def diagonals(self) -> Sequence[tuple[int, Sequence[tuple[int, int]]]]:
        """0-based (t, [(p, q)...]) groups with t = p + q ascending."""
        return diagonal_groups(
            self.num_splits, self.full_pairs,
            pair_budget=parse_pair_policy(self.pair_policy, self.num_splits,
                                          self.full_pairs))

    @property
    def num_gemms(self) -> int:
        return sum(len(p) for _, p in self.diagonals())

    def plan(self, batch_layout: str = "none") -> PipelinePlan:
        """The PipelinePlan this config resolves to (see ``tuning``)."""
        return plan_for(self, batch_layout=batch_layout)


# ----------------------------------------------------------------------------
# Driver helpers
# ----------------------------------------------------------------------------

def resolve_accuracy_config(cfg: OzakiConfig, k: int) -> OzakiConfig:
    """Resolve ``target_error``/``fast_mode`` into static schedule knobs.

    Shape-only (uses k, never the operand values), so the result is
    trace-stable: the drivers call it once per GEMM shape before sizing
    the split width. ``num_splits`` is only ever REDUCED (the configured
    count is the quality ceiling); the resolved ``pair_policy`` replaces
    a "full" policy when fast mode asks for truncation. No-op when
    neither knob is set.
    """
    if cfg.target_error is None and not cfg.fast_mode:
        return cfg
    from .accuracy import resolve_accuracy         # lazy: keeps core light
    s, policy = resolve_accuracy(
        k, cfg.num_splits, target_error=cfg.target_error,
        fast_mode=cfg.fast_mode, pair_policy=cfg.pair_policy,
        ell_acc=cfg.ell_acc, ell_in=cfg.ell_in,
        fuse=cfg.fuse_diagonals or cfg.concat_k, full_pairs=cfg.full_pairs)
    if s == cfg.num_splits and policy == cfg.pair_policy:
        return cfg
    return dataclasses.replace(cfg, num_splits=s, pair_policy=policy)


def _e_base(ea: jax.Array, eb: jax.Array) -> jax.Array:
    """Deferred per-element exponent: broadcast outer sum (int32).

    ea: (..., m) row exponents of A; eb: (..., n) row exponents of B^T.
    """
    return (ea[..., :, None].astype(jnp.int32) +
            eb[..., None, :].astype(jnp.int32))


def _from_dw(out, cfg: OzakiConfig):
    """df32 accumulator -> the f64 the paper-mode entry points return."""
    if cfg.accum == "f64":
        return out
    return out.hi.astype(jnp.float64) + out.lo.astype(jnp.float64)


def _check_dw_schedule(cfg: OzakiConfig, w: int) -> None:
    if (cfg.num_splits + 1) * w > 120:
        raise ValueError("split schedule underflows f32 scale range")


def _fold_rows(split_fn, x3, w: int) -> SplitResult:
    """Split a (B, r, k) stack by folding the batch into rows (exact:
    exponents, slices and accumulation are all row-independent)."""
    if isinstance(x3, DW):
        bsz, r, k = x3.hi.shape
        res = split_fn(DW(x3.hi.reshape(bsz * r, k),
                          x3.lo.reshape(bsz * r, k)), w)
    else:
        bsz, r, k = x3.shape
        res = split_fn(x3.reshape(bsz * r, k), w)
    if isinstance(res, StreamingSplit):
        # nothing was split: un-fold the carried operand words so the
        # batch-grid streaming kernels see (B, r, k) / (B, r) blocks
        return StreamingSplit(res.hi.reshape(bsz, r, k),
                              res.lo.reshape(bsz, r, k),
                              res.exp.reshape(bsz, r), res.w)
    s = res.slices.shape[0]
    return SplitResult(res.slices.reshape(s, bsz, r, k),
                       res.exp.reshape(bsz, r), res.w)


# ----------------------------------------------------------------------------
# Core drivers
# ----------------------------------------------------------------------------

def ozaki_matmul(a: jax.Array, b: jax.Array,
                 cfg: OzakiConfig = OzakiConfig()) -> jax.Array:
    """FP64-accurate C = A @ B via int8 GEMMs. A: (m, k) f64, B: (k, n) f64."""
    if a.dtype != jnp.float64:
        raise TypeError("ozaki_matmul takes float64; use ozaki_matmul_dw for "
                        "the TPU df32 path")
    refuse_f64_on_tpu("ozaki_matmul")
    k = a.shape[1]
    cfg = resolve_accuracy_config(cfg, k)
    w = cfg.width_for(k)
    ex = get_executor(cfg.plan())
    sa = ex.split(a, w)
    sb = ex.split(b.T, w)
    out = ex.contract(sa, sb, w, _e_base(sa.exp, sb.exp),
                      (a.shape[0], b.shape[1]))
    return _from_dw(out, cfg)


def ozaki_matmul_dw(a: DW, b_t: DW, cfg: OzakiConfig = OzakiConfig()) -> DW:
    """TPU-native path: df32 in, df32 out. ``b_t`` is B TRANSPOSED (n, k).

    Runs entirely in {int8, int32, f32}: deployable on hardware with no
    FP64 units. The number of splits should satisfy
    (num_splits + 1) * w <= 120 so all scales stay in f32 normal range.
    """
    if cfg.accum != "df32":
        cfg = dataclasses.replace(cfg, accum="df32")   # dw path IS df32
    k = a.shape[1]
    cfg = resolve_accuracy_config(cfg, k)
    w = cfg.width_for(k)
    _check_dw_schedule(cfg, w)
    ex = get_executor(cfg.plan())
    sa = ex.split_dw(a, w)
    sb = ex.split_dw(b_t, w)
    return ex.contract(sa, sb, w, _e_base(sa.exp, sb.exp),
                       (a.shape[0], b_t.shape[0]))


# ----------------------------------------------------------------------------
# Batched API: (B, m, k) @ (B, k, n), or (B, m, k) @ (k, n) broadcast weights
# ----------------------------------------------------------------------------

def _matmul_any(a: jax.Array, b: jax.Array, cfg: OzakiConfig) -> jax.Array:
    """Unbatched dispatch on input dtype: f64 paper path or f32 dw path."""
    if a.dtype == jnp.float64:
        return ozaki_matmul(a, b, cfg)
    out = ozaki_matmul_dw(DW(a, jnp.zeros_like(a)),
                          DW(b.T, jnp.zeros_like(b.T)), cfg)
    return dw_to_single(out)


def _batched_grid(a: jax.Array, b: jax.Array, cfg: OzakiConfig) -> jax.Array:
    """Fully-batched pipeline with an explicit batch dimension.

    Split folds the stack into rows, the GEMMs run the batch-grid kernel
    (Pallas backends) or a batch-dim dot_general (xla), accumulation
    broadcasts the (B, m, n) exponent base — bitwise identical to a
    Python loop over the unbatched pipeline.
    """
    f64 = a.dtype == jnp.float64
    if not f64 and cfg.accum != "df32":
        cfg = dataclasses.replace(cfg, accum="df32")
    bsz, m, k = a.shape
    n = b.shape[-1]
    cfg = resolve_accuracy_config(cfg, k)
    w = cfg.width_for(k)
    if not f64:
        _check_dw_schedule(cfg, w)
    ex = get_executor(cfg.plan(batch_layout="grid"))
    b_t = jnp.swapaxes(b, 1, 2)                        # (B, n, k)
    if f64:
        sa = _fold_rows(ex.split, a, w)
        sb = _fold_rows(ex.split, b_t, w)
    else:
        sa = _fold_rows(ex.split_dw, DW(a, jnp.zeros_like(a)), w)
        sb = _fold_rows(ex.split_dw, DW(b_t, jnp.zeros_like(b_t)), w)
    out = ex.contract(sa, sb, w, _e_base(sa.exp, sb.exp), (bsz, m, n))
    if f64:
        return _from_dw(out, cfg)
    return dw_to_single(out)


@functools.partial(jax.custom_jvp, nondiff_argnums=(2,))
def _batched_core(a: jax.Array, b: jax.Array, cfg: OzakiConfig) -> jax.Array:
    if b.ndim == 2:
        # Broadcast weights: fold the batch into rows. Exact — exponents,
        # slices and accumulation are all row-independent, so this equals
        # a loop over ``ozaki_matmul`` bitwise (and is one big MXU GEMM).
        bsz, m, k = a.shape
        out = _matmul_any(a.reshape(bsz * m, k), b, cfg)
        return out.reshape(bsz, m, b.shape[1])
    return _batched_grid(a, b, cfg)


@_batched_core.defjvp
def _batched_core_jvp(cfg, primals, tangents):
    a, b = primals
    da, db = tangents
    primal = _batched_core(a, b, cfg)
    # The scheme reproduces the exact product, so the product rule applies
    # verbatim. Tangents run on the plain matmul (they need only the
    # working precision of the inputs, not the emulated one).
    tangent = (jnp.matmul(da, b, preferred_element_type=a.dtype) +
               jnp.matmul(a, db, preferred_element_type=a.dtype))
    return primal, tangent.astype(primal.dtype)


def ozaki_matmul_batched(a: jax.Array, b: jax.Array,
                         cfg: OzakiConfig = OzakiConfig()) -> jax.Array:
    """Batched Ozaki GEMM: ``C[i] = A[i] @ B[i]`` (or shared ``B``).

    a: (B, m, k); b: (B, k, n), or (k, n) to broadcast one weight matrix
    over the batch (the serving case). f64 inputs follow ``cfg.accum``;
    f32 inputs run the TPU-native df32 pipeline and return f32. The
    result is differentiable (exact-product JVP) and jit-stable — pass
    ``cfg`` statically when jitting.
    """
    if a.ndim != 3:
        raise ValueError(f"a must be (batch, m, k), got {a.shape}")
    if b.ndim not in (2, 3):
        raise ValueError(f"b must be (k, n) or (batch, k, n), got {b.shape}")
    if b.ndim == 3 and a.shape[0] != b.shape[0]:
        raise ValueError(f"batch mismatch: {a.shape} vs {b.shape}")
    if a.shape[-1] != b.shape[-2]:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape}")
    if a.dtype != b.dtype:
        raise TypeError(f"dtype mismatch: {a.dtype} vs {b.dtype}")
    if a.dtype == jnp.float64:
        refuse_f64_on_tpu("ozaki_matmul_batched")
    return _batched_core(a, b, cfg)


# ----------------------------------------------------------------------------
# Complex GEMM (quantum-circuit simulation support, Sec. 4.4)
# ----------------------------------------------------------------------------

def ozaki_matmul_complex(a: jax.Array, b: jax.Array,
                         cfg: OzakiConfig = OzakiConfig(),
                         algo: str = "4mul") -> jax.Array:
    """complex128 C = A @ B with real/imag separated at split time.

    ``algo="4mul"``: Cr = ArBr - AiBi, Ci = ArBi + AiBr (paper's approach —
    each of the 4 real matrices is split exactly once, products reused).
    ``algo="3mul"``: Karatsuba, one fewer real GEMM group at slightly wider
    exponent range (beyond-paper option).
    """
    refuse_f64_on_tpu("ozaki_matmul_complex")
    ar, ai = jnp.real(a), jnp.imag(a)
    br, bi = jnp.real(b), jnp.imag(b)
    k = a.shape[1]
    cfg = resolve_accuracy_config(cfg, k)
    w = cfg.width_for(k)
    ex = get_executor(cfg.plan())

    def real_mm(x_split, y_split, shape):
        out = ex.contract(x_split, y_split, w,
                          _e_base(x_split.exp, y_split.exp), shape)
        return _from_dw(out, cfg)

    def split(x):
        return ex.split(x, w)

    shape = (a.shape[0], b.shape[1])
    if algo == "3mul":
        s_ar = split(ar)
        s_ai = split(ai)
        s_as = split(ar + ai)
        s_br = split(br.T)
        s_bi = split(bi.T)
        s_bs = split((br + bi).T)
        p1 = real_mm(s_ar, s_br, shape)
        p2 = real_mm(s_ai, s_bi, shape)
        p3 = real_mm(s_as, s_bs, shape)
        return jax.lax.complex(p1 - p2, p3 - p1 - p2)

    s_ar = split(ar)
    s_ai = split(ai)
    s_br = split(br.T)
    s_bi = split(bi.T)
    c_r = real_mm(s_ar, s_br, shape) - real_mm(s_ai, s_bi, shape)
    c_i = real_mm(s_ar, s_bi, shape) + real_mm(s_ai, s_br, shape)
    return jax.lax.complex(c_r, c_i)


# ----------------------------------------------------------------------------
# Reference paths for comparison (the paper's baselines)
# ----------------------------------------------------------------------------

def dgemm_f64(a: jax.Array, b: jax.Array) -> jax.Array:
    """Plain FP64 GEMM (cuBLAS-DGEMM stand-in on CPU)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float64)


def gemm_fp32_pass(a: jax.Array, b: jax.Array) -> jax.Array:
    """Naive single-f32 GEMM of f64 data — the accuracy anti-baseline."""
    return jnp.dot(a.astype(jnp.float32),
                   b.astype(jnp.float32)).astype(jnp.float64)
