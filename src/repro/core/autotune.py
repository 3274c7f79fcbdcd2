"""Measurement-driven autotuning for the Ozaki pipeline, with a
persistent plan cache.

The analytic planner (``core.tuning.select_pipeline_plan``) is a
VMEM-budget model: good enough to never launch an illegal kernel, blind
to everything the follow-up literature (arXiv:2409.13313,
arXiv:2508.03984) shows actually separates implementations — measured
launch overheads, the fusion-mode crossover, concat-k amortization. This
module closes that gap in three pieces:

* ``candidate_plans`` — enumerate ``PipelinePlan`` candidates around the
  analytic seed: tile shapes (halved GEMM blocks down to their alignment
  floors), fusion mode (epilogue- vs stage-fused), and the ``concat_k``
  schedule. By default every candidate is **result-invariant**: tiles
  and fusion modes are bitwise-neutral (enforced by the backend-parity
  suite) and ``concat_k`` regroups exact int32 sums, so a tuned plan's
  results are bitwise-equal to the analytic plan's. ``search_num_splits``
  widens the space to split counts *above* the accuracy target's minimum
  (never below — the paper's operating point is a floor); those
  candidates trade bitwise reproducibility for generality and are off by
  default.
* ``measure_plan`` / ``autotune_plan`` — time each candidate on the live
  backend with warm-up (covers jit compile) and ``block_until_ready``,
  then pick the measured best. The analytic plan is always candidate #0,
  so the tuned result is never worse than analytic modulo timer noise.
* ``PlanCache`` — a versioned JSON file mapping
  ``(m, n, k, batch, dtype, backend, device_kind)`` to the measured-best
  ``PipelinePlan`` (reusing ``PipelinePlan.to_dict/from_dict``).
  ``select_pipeline_plan`` consults it (hit returns without re-tuning;
  miss falls back to the analytic plan unless ``autotune=True``), and
  ``serving.engine`` pre-warms it at startup so steady-state serving
  never tunes on the request path. Version mismatches and corrupted
  files degrade to an empty cache (analytic planning), never an error.

An ambient-cache registry (``use_plan_cache``) mirrors
``parallel.ozaki_shard``'s mesh registry: the serving engine scopes its
cache around each tick, and ``models.layers`` picks cached plans up at
trace time without threading the cache through every call site.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import tempfile
import time
import warnings
from typing import Callable, Optional, Sequence

import numpy as np

from repro.kernels.launch import LANE, SUBLANE_I8

from .analytic import DGEMM_MANTISSA_SPACE, INT8_INT32
from .splitting import slice_width
from .tuning import (CONCAT_K_MAX, PipelinePlan, _cached_hit_acceptable,
                     diagonal_groups, plan_schedule_ok, select_num_splits,
                     select_pipeline_plan)
from .warn_once import WarnOnceLatch

__all__ = ["PLAN_CACHE_VERSION", "PlanKey", "PlanCache", "plan_cache_key",
           "candidate_plans", "measure_plan", "autotune_plan",
           "AutotuneReport", "use_plan_cache", "active_plan_cache",
           "set_plan_cache", "warn_if_interpret_ranked"]

# v3: keys carry the emulation ``scheme`` (Scheme I slice pairs vs
# Scheme II residue GEMMs — ``tuning.PLAN_SCHEMES``), so tuned winners
# from the two families never collide under one key. v2 entries predate
# the scheme field and load as empty (the standard fallback-to-empty
# path — analytic plans until re-tuned).
# v2: entries carry a ``meta`` dict recording the measurement mode
# (``{"interpret": bool | None}``). v1 files load as empty — the old
# entries were indistinguishable from hardware-measured plans, which is
# exactly the bug that bump fixed.
PLAN_CACHE_VERSION = 3

# Warns (once per cache key) when a compiled run is served a plan whose
# measurement ranking ran in Pallas interpret mode: interpret timings
# order candidates but are not Mosaic timings, so the ranking deserves a
# re-tune on hardware. Registered in ``warn_once`` so the test suite's
# ``reset_all_warn_latches`` covers it.
_INTERPRET_LATCH = WarnOnceLatch("interpret_ranked_plans")


def default_device_kind() -> str:
    """The accelerator identity plans are tuned for (cache key part)."""
    try:
        import jax
        return jax.devices()[0].device_kind
    except Exception:                                   # pragma: no cover
        return "unknown"


def _dtype_name(dtype) -> str:
    """Canonical dtype name: ``jnp.float64`` objects, ``np.dtype``s and
    ``"float64"`` strings all map to the same key string."""
    try:
        return np.dtype(dtype).name
    except TypeError:                       # e.g. bfloat16 via ml_dtypes
        import jax.numpy as jnp
        return jnp.dtype(dtype).name


def _canon_dtype(dtype, accum: str) -> str:
    """Normalize the operand dtype key; default it from the accum mode."""
    if dtype is None:
        return "float64" if accum == "f64" else "float32"
    return _dtype_name(dtype)


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Cache identity of one tuned GEMM: shape, operand dtype, backend,
    and the device kind the measurement ran on (hashable).

    ``dtype`` is canonicalized at construction (``jnp.dtype(...).name``
    semantics): a key built from the ``jnp.float64`` *object* and one
    built from the ``"float64"`` *string* are the same key — before the
    canonicalization they hashed differently and silently missed the
    cache (and broke JSON serialization of ``to_dict``).
    """

    m: int
    n: int
    k: int
    batch: int = 1
    dtype: str = "float64"
    backend: str = "pallas_fused"
    device_kind: str = "cpu"
    scheme: str = "ozaki_fp64"

    def __post_init__(self):
        if not isinstance(self.dtype, str) or self.dtype != \
                _dtype_name(self.dtype):
            object.__setattr__(self, "dtype", _dtype_name(self.dtype))

    def encode(self) -> str:
        return (f"m={self.m};n={self.n};k={self.k};batch={self.batch};"
                f"dtype={self.dtype};backend={self.backend};"
                f"device={self.device_kind};scheme={self.scheme}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "PlanKey":
        return cls(**d)


def plan_cache_key(m: int, n: int, k: int, *, batch: int = 1,
                   dtype=None, accum: str = "df32",
                   backend: str = "pallas_fused",
                   device_kind: Optional[str] = None,
                   scheme: str = "ozaki_fp64") -> PlanKey:
    """The key ``select_pipeline_plan`` and the engine pre-warm agree on."""
    return PlanKey(m=m, n=n, k=k, batch=batch,
                   dtype=_canon_dtype(dtype, accum), backend=backend,
                   device_kind=device_kind or default_device_kind(),
                   scheme=scheme)


class PlanCache:
    """Persistent measured-plan store: one JSON file per deployment.

    File format (``version`` guards schema drift — a mismatch or a
    corrupted file loads as an EMPTY cache with a warning, so planning
    falls back to the analytic model instead of failing)::

        {"version": 2,
         "plans": {"m=..;n=..;..": {"key": {...PlanKey...},
                                    "plan": {...PipelinePlan.to_dict...},
                                    "us": 123.4,
                                    "meta": {"interpret": true}}}}

    Entries are decoded from the structured ``key`` dict (the string key
    is display/dedup only). ``meta`` records how the entry's measurement
    ran — today ``interpret`` (Pallas interpret mode vs compiled Mosaic;
    None for entries stored without measurement) — so consumers can tell
    a CPU-interpret ranking from a hardware one
    (``warn_if_interpret_ranked``). ``hits``/``misses`` count ``get``
    outcomes for the pre-warm/steady-state tests and ops introspection.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = os.fspath(path) if path is not None else None
        self._plans: dict[PlanKey, PipelinePlan] = {}
        self._us: dict[PlanKey, Optional[float]] = {}
        self._meta: dict[PlanKey, dict] = {}
        self.hits = 0
        self.misses = 0

    # ---- persistence ---------------------------------------------------
    @classmethod
    def load(cls, path) -> "PlanCache":
        """Load a cache file; missing/corrupted/wrong-version -> empty."""
        cache = cls(path)
        if not os.path.exists(cache.path):
            return cache
        try:
            with open(cache.path) as f:
                data = json.load(f)
            version = data.get("version")
            if version != PLAN_CACHE_VERSION:
                warnings.warn(
                    f"plan cache {cache.path}: version {version!r} != "
                    f"{PLAN_CACHE_VERSION}; starting from an empty cache "
                    "(analytic plans until re-tuned)")
                return cache
            for entry in data.get("plans", {}).values():
                key = PlanKey.from_dict(entry["key"])
                cache._plans[key] = PipelinePlan.from_dict(entry["plan"])
                cache._us[key] = entry.get("us")
                cache._meta[key] = dict(entry.get("meta") or {})
        except (OSError, ValueError, KeyError, TypeError) as e:
            warnings.warn(f"plan cache {cache.path}: unreadable "
                          f"({type(e).__name__}: {e}); starting from an "
                          "empty cache (analytic plans until re-tuned)")
            cache._plans.clear()
            cache._us.clear()
            cache._meta.clear()
        return cache

    def save(self, path: Optional[str] = None) -> Optional[str]:
        """Atomically write the cache (tmp file + rename); no-op without
        a path. Returns the path written."""
        path = os.fspath(path) if path is not None else self.path
        if path is None:
            return None
        data = {"version": PLAN_CACHE_VERSION, "plans": {
            key.encode(): {"key": key.to_dict(),
                           "plan": self._plans[key].to_dict(),
                           "us": self._us.get(key),
                           "meta": self._meta.get(key, {})}
            for key in sorted(self._plans, key=lambda kk: kk.encode())}}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        self.path = path
        return path

    # ---- store ---------------------------------------------------------
    def get(self, key: PlanKey) -> Optional[PipelinePlan]:
        plan = self._plans.get(key)
        if plan is None:
            self.misses += 1
        else:
            self.hits += 1
        return plan

    def put(self, key: PlanKey, plan: PipelinePlan,
            measured_us: Optional[float] = None,
            interpret: Optional[bool] = None) -> None:
        self._plans[key] = plan
        self._us[key] = measured_us
        self._meta[key] = {"interpret": interpret}

    def measured_us(self, key: PlanKey) -> Optional[float]:
        return self._us.get(key)

    def meta(self, key: PlanKey) -> dict:
        """Measurement metadata of one entry ({} when unknown)."""
        return self._meta.get(key, {})

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._plans

    def keys(self):
        return self._plans.keys()


def warn_if_interpret_ranked(cache: PlanCache, key: PlanKey,
                             interpret: bool) -> None:
    """Warn once per key when a compiled consumer gets an interpret-ranked
    plan.

    Called on every cache-hit path (``select_pipeline_plan`` and
    ``autotune_plan``) with the CONSUMER's interpret mode: a compiled run
    (``interpret=False``) served a plan whose candidate ranking was timed
    in Pallas interpret mode is running an ordering CPU emulation picked —
    bitwise-correct, but not a Mosaic timing. Entries with unknown
    provenance (``meta`` absent: stored without measurement, or loaded
    from a pre-v2 cache) stay silent.
    """
    if interpret:
        return
    if cache.meta(key).get("interpret"):
        _INTERPRET_LATCH.warn(
            key.encode(),
            f"plan cache {cache.path or '<memory>'}: plan for "
            f"[{key.encode()}] was ranked in Pallas interpret mode but is "
            "being consumed by a compiled run — re-tune on hardware "
            "(autotune with interpret=False) for a trustworthy ranking")


# ----------------------------------------------------------------------------
# Ambient cache registry (mirrors parallel.ozaki_shard's mesh registry)
# ----------------------------------------------------------------------------

_PLAN_CACHE: list = [None]


def set_plan_cache(cache: Optional[PlanCache]) -> None:
    """Register (or clear, with None) the ambient plan cache.

    Trace-time semantics, exactly like the shard-mesh registry: jitted
    model steps read the registry while TRACING, so the cache must be
    registered before the first call of any step that should honor it.
    The serving engine scopes its cache around every tick
    (``use_plan_cache``), which covers the first trace by construction.
    """
    _PLAN_CACHE[0] = cache


def active_plan_cache() -> Optional[PlanCache]:
    return _PLAN_CACHE[0]


@contextlib.contextmanager
def use_plan_cache(cache: Optional[PlanCache]):
    prev = _PLAN_CACHE[0]
    _PLAN_CACHE[0] = cache
    try:
        yield cache
    finally:
        _PLAN_CACHE[0] = prev


# ----------------------------------------------------------------------------
# Candidate enumeration
# ----------------------------------------------------------------------------

def _tile_variants(tile):
    """Halved-block launch variants of one TilePlan (result-invariant)."""
    out = []
    if tile.bk > LANE:
        out.append(dataclasses.replace(tile, bk=tile.bk // 2))
    if tile.bm > SUBLANE_I8:
        out.append(dataclasses.replace(tile, bm=tile.bm // 2))
    if tile.bn > LANE:
        out.append(dataclasses.replace(tile, bn=tile.bn // 2))
    return out


def candidate_plans(m: int, n: int, k: int, *, batch: int = 1,
                    broadcast_weights: bool = False,
                    backend: str = "pallas_fused", accum: str = "df32",
                    num_splits: Optional[int] = None,
                    fuse_epilogue: bool = True,
                    streaming: bool = False,
                    shard_axis: Optional[str] = None,
                    comm: str = "f64",
                    interpret: Optional[bool] = None,
                    search_num_splits: int = 0,
                    target_error: Optional[float] = None,
                    fast_mode: bool = False,
                    pair_policy: Optional[str] = None,
                    max_candidates: Optional[int] = None,
                    scheme: str = "ozaki_fp64",
                    num_moduli: Optional[int] = None,
                    cross_scheme: bool = True,
                    **analytic_kwargs) -> list[PipelinePlan]:
    """Enumerate candidate plans around the analytic seed.

    The analytic plan is always first. Default candidates vary only
    launch-level knobs — GEMM tile shapes, fusion mode (epilogue vs
    stages), ``concat_k`` — all of which leave results bitwise unchanged
    (exact int32 regrouping / parity-tested kernel fusions), so any
    cached winner reproduces the analytic plan's output bit for bit.
    ``search_num_splits=j`` additionally tries ``s_min+1 .. s_min+j``
    splits (still within the accuracy target: more slices is strictly
    more mantissa space); those change the rounding stream and are off
    by default. With ``target_error`` set, pair-budget variants are also
    enumerated — every one checked against the guaranteed error bound
    (``core.accuracy.truncation_eta``), so no candidate the measurement
    ranks can violate the configured target. Every non-seed candidate is
    filtered through ``tuning.plan_schedule_ok``: a df32 plan violating
    ``(num_splits + 1) * w <= 120`` would crash mid-measurement, so it
    never enters the search space. ``max_candidates`` truncates AFTER
    dedup, keeping the analytic seed. ``analytic_kwargs``
    (``mantissa_space``/``mmu``/``vmem_budget``) reach the analytic seed
    planner unchanged.

    Cross-scheme search: when ``target_error`` pins an accuracy contract
    and ``cross_scheme`` is on, the OTHER scheme family's analytic seed
    joins the candidate list — a Scheme I search (f64 accumulation only;
    the residue path reconstructs through FP64 CRT) enumerates the
    matching Scheme II operating point and vice versa, so the
    measurement arbitrates between the families for real instead of
    trusting the GEMM-count model. Both seeds guarantee the same target,
    so any winner honors the contract.
    """
    analytic = functools.partial(
        select_pipeline_plan, m, n, k, batch=batch,
        broadcast_weights=broadcast_weights, backend=backend, accum=accum,
        num_splits=num_splits, shard_axis=shard_axis, comm=comm,
        interpret=interpret, target_error=target_error,
        fast_mode=fast_mode, pair_policy=pair_policy, scheme=scheme,
        num_moduli=num_moduli, **analytic_kwargs)
    base = analytic(fuse_epilogue=fuse_epilogue, streaming=streaming)
    cands = [base]

    def add(plan: PipelinePlan):
        if plan not in cands and plan_schedule_ok(plan, k):
            cands.append(plan)

    if scheme == "ozaki2_fp64":
        # the residue path has no pair schedule; the launch-level space
        # is the stages <-> epilogue fusion flip (both bitwise-equal:
        # the fused-CRT kernel replays the reference Garner digits and
        # ascending-radix f64 sum) and the GEMM tile shapes, plus (under
        # a target) the Scheme I seed for cross-family arbitration
        if base.fusion in ("stages", "epilogue"):
            add(dataclasses.replace(
                base, fusion=("epilogue" if base.fusion == "stages"
                              else "stages")))
        for seed in list(cands):
            for tile in _tile_variants(seed.tile):
                add(dataclasses.replace(seed, tile=tile))
        if target_error is not None and cross_scheme and \
                shard_axis is None:
            add(select_pipeline_plan(
                m, n, k, batch=batch, broadcast_weights=broadcast_weights,
                backend=backend, accum="f64",
                fuse_epilogue=fuse_epilogue, streaming=streaming,
                interpret=interpret, target_error=target_error,
                **analytic_kwargs))
        if max_candidates is not None and len(cands) > max_candidates:
            cands = cands[:max_candidates]
        return cands

    if target_error is not None and cross_scheme and accum == "f64" and \
            shard_axis is None:
        try:
            add(select_pipeline_plan(
                m, n, k, batch=batch, broadcast_weights=broadcast_weights,
                backend=backend, interpret=interpret,
                target_error=target_error, scheme="ozaki2_fp64",
                **analytic_kwargs))
        except ValueError:
            pass            # moduli pool exhausted: no Scheme II point

    # fusion-mode flips (pallas_fused only; all modes bitwise-equal —
    # streaming included, so the measurement decides whether eliminating
    # the HBM slice stacks beats re-reading the operand words per group)
    # Each flip takes the GEMM blocks planned for its own kernel family:
    # the epilogue's outgrow what the other families' VMEM holds.
    if base.fusion in ("stages", "epilogue", "streaming"):
        for flip in ("stages", "epilogue", "streaming"):
            if flip != base.fusion:
                tile = analytic(fuse_epilogue=flip == "epilogue",
                                streaming=flip == "streaming").tile
                add(dataclasses.replace(base, fusion=flip, tile=tile))

    # comm-transport flip (sharded shapes only): both transports are
    # bitwise-equal to the single-device reference (integer collectives
    # are associative), so the measurement is free to pick either — on
    # a single-device measurement host the flip is a no-op to execute
    # but the cached winner carries the transport for the deployment
    if base.shard_axis is not None:
        for flip in ("f64", "int8"):
            if flip != base.comm:
                add(dataclasses.replace(base, comm=flip))

    # concat_k flip: exact int32 regrouping; never for a stacked batch
    # (the concatenated operands would materialize once per batch row)
    if base.fuse_diagonals and k <= CONCAT_K_MAX and \
            base.batch_layout != "grid":
        add(dataclasses.replace(base, concat_k=not base.concat_k))

    # halved GEMM tiles, crossed with every schedule/fusion seed so far
    for seed in list(cands):
        for tile in _tile_variants(seed.tile):
            add(dataclasses.replace(seed, tile=tile))

    # pair-budget variants (accuracy-checked ONLY: each must meet the
    # target's guaranteed bound) — whole-diagonal budgets between the
    # seed's resolved policy and the full schedule, so the measurement
    # can trade kept pairs for time without ever crossing the target
    if target_error is not None:
        from .accuracy import kept_pairs, truncation_eta   # lazy: no cycle
        s = base.num_splits
        fuse = base.fuse_diagonals or base.concat_k
        w = slice_width(k, fuse_terms=s if fuse else 1)
        groups_seen = 0
        for _, pairs in diagonal_groups(s, base.full_pairs):
            groups_seen += len(pairs)
            policy = f"budget:{groups_seen}"
            eta = truncation_eta(s, w, pair_policy=policy,
                                 full_pairs=base.full_pairs)
            if k * eta <= target_error:
                total = len(kept_pairs(s, full_pairs=base.full_pairs))
                if groups_seen < total:
                    add(dataclasses.replace(base, pair_policy=policy))
        if base.pair_policy != "full":
            # the untruncated schedule is always at least as accurate as
            # the resolved budget: let the measurement decline truncation
            add(dataclasses.replace(base, pair_policy="full"))

    # wider split counts stay within the accuracy target (s >= s_min)
    for extra in range(1, search_num_splits + 1):
        add(dataclasses.replace(base, num_splits=base.num_splits + extra))

    if max_candidates is not None and len(cands) > max_candidates:
        cands = cands[:max_candidates]
    return cands


# ----------------------------------------------------------------------------
# Measurement
# ----------------------------------------------------------------------------

def _make_operands(m: int, n: int, k: int, *, batch: int,
                   broadcast_weights: bool, dtype: str, seed: int = 0):
    """Representative operands (paper Eq. 6-style spread, phi=1)."""
    rng = np.random.default_rng(seed)

    def mat(r, c):
        x = (rng.uniform(-0.5, 0.5, (r, c))
             * np.exp(rng.standard_normal((r, c))))
        return x.astype(dtype)

    if batch <= 1 and not broadcast_weights:
        return mat(m, k), mat(k, n)
    a = np.stack([mat(m, k) for _ in range(batch)])
    if broadcast_weights:
        return a, mat(k, n)
    return a, np.stack([mat(k, n) for _ in range(batch)])


def _plan_runner(plan: PipelinePlan, a, b) -> Callable[[], object]:
    """A zero-arg callable running one GEMM under ``plan``.

    Applies the plan through the public driver (``apply_pipeline_plan``
    -> ``OzakiConfig``), so the measurement exercises exactly the code
    path a deployment with the cached plan runs.
    """
    import jax.numpy as jnp

    from .ozaki import OzakiConfig, ozaki_matmul, ozaki_matmul_batched
    from .tuning import apply_pipeline_plan

    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if getattr(plan, "scheme", "ozaki_fp64") == "ozaki2_fp64":
        from .modular import (ModularConfig, ozaki2_matmul,
                              ozaki2_matmul_batched)
        mcfg = ModularConfig(beta=plan.beta, num_moduli=plan.num_moduli,
                             backend=plan.backend,
                             fuse_epilogue=(plan.fusion == "epilogue"),
                             interpret=plan.interpret, tile=plan.tile)
        if a.ndim == 3:
            return lambda: ozaki2_matmul_batched(a, b, mcfg)
        return lambda: ozaki2_matmul(a, b, mcfg)
    cfg = apply_pipeline_plan(OzakiConfig(), plan)
    if a.ndim == 3:
        return lambda: ozaki_matmul_batched(a, b, cfg)
    if str(a.dtype) == "float64":
        return lambda: ozaki_matmul(a, b, cfg)
    # f32 operands: the TPU-native path via the batched API's rows fold
    return lambda: ozaki_matmul_batched(a[None], b, cfg)[0]


def measure_plan(plan: PipelinePlan, m: int, n: int, k: int, *,
                 batch: int = 1, broadcast_weights: bool = False,
                 dtype: Optional[str] = None, warmup: int = 1,
                 iters: int = 3, seed: int = 0,
                 operands=None) -> float:
    """Median wall-time (us) of one GEMM under ``plan`` on the live
    backend. Warm-up runs (jit compile included) and every timed run
    ``block_until_ready`` so device work is fully counted."""
    import jax

    dtype = _canon_dtype(dtype, plan.accum)
    if operands is None:
        operands = _make_operands(m, n, k, batch=batch,
                                  broadcast_weights=broadcast_weights,
                                  dtype=dtype, seed=seed)
    fn = _plan_runner(plan, *operands)
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn())
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


@dataclasses.dataclass(frozen=True)
class AutotuneReport:
    """Outcome of one autotune run: the winner plus every measurement."""

    key: PlanKey
    best: PipelinePlan
    best_us: float
    measurements: tuple          # ((plan, us), ...) in candidate order

    @property
    def analytic_us(self) -> float:
        return self.measurements[0][1]       # candidate #0 is analytic


def autotune_plan(m: int, n: int, k: int, *, batch: int = 1,
                  broadcast_weights: bool = False,
                  backend: str = "pallas_fused", accum: str = "df32",
                  num_splits: Optional[int] = None,
                  fuse_epilogue: bool = True,
                  streaming: bool = False,
                  shard_axis: Optional[str] = None,
                  comm: str = "f64", interpret: Optional[bool] = None,
                  target_error: Optional[float] = None,
                  fast_mode: bool = False,
                  pair_policy: Optional[str] = None,
                  dtype: Optional[str] = None,
                  device_kind: Optional[str] = None,
                  cache: Optional[PlanCache] = None,
                  candidates: Optional[Sequence[PipelinePlan]] = None,
                  max_candidates: Optional[int] = 8, warmup: int = 1,
                  iters: int = 3, save: bool = True,
                  scheme: str = "ozaki_fp64",
                  num_moduli: Optional[int] = None,
                  **analytic_kwargs) -> AutotuneReport:
    """Measure candidate plans and return the best (stored in ``cache``).

    The cache is consulted first (a hit at the SAME accuracy operating
    point — explicit ``num_splits`` must match the cached plan's, and
    when ``target_error``/``fast_mode``/``pair_policy`` pin a pair
    policy, the cached plan's policy must match the resolved one — skips
    measurement entirely); the winner is ``put`` under the shared key
    and — when the cache has a backing path and ``save`` — persisted
    immediately, so a crash after tuning N of M shapes keeps the N
    measured plans.
    """
    from repro.kernels.launch import resolve_interpret
    interpret = resolve_interpret(interpret)
    accuracy_pinned = (target_error is not None or fast_mode or
                       pair_policy is not None)
    if scheme == "ozaki2_fp64":
        accum = "f64"
        if num_moduli is None:
            from .modular import resolve_modular    # lazy: no cycle
            num_moduli = len(resolve_modular(
                k, target_error=target_error,
                mantissa_space=analytic_kwargs.get(
                    "mantissa_space", DGEMM_MANTISSA_SPACE)).moduli)
    elif accuracy_pinned:
        from .accuracy import resolve_accuracy      # lazy: no cycle
        base_s = (num_splits if num_splits is not None else
                  select_num_splits(
                      k,
                      mantissa_space=analytic_kwargs.get(
                          "mantissa_space", DGEMM_MANTISSA_SPACE),
                      mmu=analytic_kwargs.get("mmu", INT8_INT32)))
        num_splits, pair_policy = resolve_accuracy(
            k, base_s, target_error=target_error, fast_mode=fast_mode,
            pair_policy=pair_policy if pair_policy is not None else "full")
    dtype = _canon_dtype(dtype, accum)
    key = plan_cache_key(m, n, k, batch=batch, dtype=dtype, accum=accum,
                         backend=backend, device_kind=device_kind,
                         scheme=scheme)
    if cache is not None:
        hit = cache.get(key)
        # same acceptance rule as select_pipeline_plan: under a pinned
        # target ANY cached point meeting the bound hits (the measured
        # winner may carry more pairs/splits than the minimal resolved
        # point — rejecting it would re-measure on every call)
        if hit is not None and _cached_hit_acceptable(
                hit, k, num_splits=num_splits, target_error=target_error,
                accuracy_pinned=accuracy_pinned,
                policy=pair_policy if pair_policy is not None else "full",
                scheme=scheme, num_moduli=num_moduli):
            warn_if_interpret_ranked(cache, key, interpret)
            return AutotuneReport(key=key, best=hit,
                                  best_us=cache.measured_us(key) or 0.0,
                                  measurements=((hit, 0.0),))
    if candidates is None:
        candidates = candidate_plans(
            m, n, k, batch=batch, broadcast_weights=broadcast_weights,
            backend=backend, accum=accum, num_splits=num_splits,
            fuse_epilogue=fuse_epilogue, streaming=streaming,
            shard_axis=shard_axis, comm=comm,
            interpret=interpret, target_error=target_error,
            pair_policy=pair_policy, max_candidates=max_candidates,
            scheme=scheme, num_moduli=num_moduli, **analytic_kwargs)
    operands = _make_operands(m, n, k, batch=batch,
                              broadcast_weights=broadcast_weights,
                              dtype=dtype)
    measurements = []
    for plan in candidates:
        us = measure_plan(plan, m, n, k, batch=batch,
                          broadcast_weights=broadcast_weights, dtype=dtype,
                          warmup=warmup, iters=iters, operands=operands)
        measurements.append((plan, us))
    best, best_us = min(measurements, key=lambda pu: pu[1])
    if cache is not None:
        cache.put(key, best, measured_us=best_us, interpret=interpret)
        if save and cache.path is not None:
            cache.save()
    return AutotuneReport(key=key, best=best, best_us=best_us,
                          measurements=tuple(measurements))
