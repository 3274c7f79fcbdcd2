"""The package's front door: one matmul, one precision policy.

The paper's pitch is that the Ozaki scheme is a *drop-in* DGEMM: callers
ask for an accuracy and the scheme decides splits, kernels, and
truncation. Four PRs of growth left that decision spread over four entry
points (``ozaki_matmul``/``_batched``/``_dw``/``_complex``), eight
``ozaki_*`` ArchConfig fields, and six serving-engine kwargs. This
module collapses all of it into two objects:

* ``MatmulPolicy`` — a frozen, hashable bundle of every precision
  decision (scheme, backend, split count, fusion, accuracy target, fast
  mode, sharding, plan cache), with a compact string spec that parses,
  formats canonically, and JSON-round-trips::

      ozaki-fp64                      # the paper, auto split count
      ozaki-fp64x9                    # pinned INT8x9 operating point
      ozaki-fp64@1e-25:fast/pallas_fused+epilogue
      ozaki-fp64x9/pallas_fused+streaming   # slices never leave VMEM
      ozaki-fp64x7:budget:12/pallas|shard=data|cache=plans.json|autotune
      bf16                            # the TPU-native baseline
      int8-quant                      # lossy inference quantization

  Grammar (sections in fixed order, every one optional but the scheme)::

      SPEC    := SCHEME ["x" SPLITS] ["@" TARGET] [":" MODES]
                 ["/" BACKEND ["+epilogue" | "+streaming"]] ("|" OPTION)*
      MODES   := MODE ("," MODE)*   MODE := "fast" | "full" | "diagonal"
                                          | "budget:" N
      OPTION  := "shard=" AXIS | "comm=" ("f64" | "int8")
                 | "cache=" PATH | "autotune"

* ``matmul(a, b, precision=...)`` — one entry point dispatching on
  rank/dtype/DW-ness to the existing pipelines (which stay the
  bitwise-verified implementation layer): 2-D f64 -> the paper path,
  2-D f32 -> the TPU-native df32 path, 3-D -> the batched pipeline
  (stacked or broadcast weights), ``DW`` operands -> the double-float32
  entry, complex -> the 4-mul complex pipeline.

``default_matmul_precision(spec)`` mirrors ``jax.default_matmul_precision``:
a context manager scoping the ambient policy — and, when the policy
names a plan cache, the ambient ``core.autotune`` plan-cache registry —
around a region of code, so libraries can call ``repro.matmul`` without
threading a policy argument.

Validation that used to live in ``OzakiConfig.__post_init__``,
``ArchConfig``'s asserts, and ``launch/serve.py`` flag handling is
centralized in ``MatmulPolicy.__post_init__``: unknown schemes/backends,
malformed pair policies, non-positive targets, and ozaki-only knobs on
non-ozaki schemes are all rejected at policy construction, before any
array exists.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import re
import threading
from typing import Optional

from repro import tracing

SCHEMES = ("bf16", "int8_quant", "ozaki_fp64", "ozaki2_fp64")

_SCHEME_RE = re.compile(r"^(?P<scheme>[a-z0-9_\-]+?)(?:x(?P<splits>\d+))?$")


def _canon_scheme(s: str) -> str:
    return s.replace("-", "_")


def _canon_backend(s: str) -> str:
    return s.replace("-", "_")


@dataclasses.dataclass(frozen=True)
class MatmulPolicy:
    """One precision decision for every matmul it governs (hashable).

    scheme:        "bf16" | "int8_quant" | "ozaki_fp64" — what the matmul
                   computes (baseline, lossy quantization, or the paper's
                   FP64-accurate int8 scheme).
    backend:       "xla" | "pallas" | "pallas_fused" — executor family
                   (ozaki only; see ``core.tuning.BACKENDS``).
    num_splits:    s in INT8xs, or None for the shape-derived paper
                   operating point (``core.tuning.select_num_splits``).
    fuse_epilogue: pallas_fused: GEMM + scaled accumulation in one kernel
                   (int32 slice products never reach HBM).
    streaming:     pallas_fused: slice EXTRACTION fused into the epilogue
                   GEMM grid too — int8 slices live only in VMEM, never
                   written to or re-read from HBM (``fusion="streaming"``;
                   spec suffix ``+streaming``). Mutually exclusive with
                   ``fuse_epilogue`` (it subsumes it).
    target_error:  accuracy target on the scaled error (``core.accuracy``)
                   — lets the planner REDUCE the split count per shape.
    fast_mode:     truncate slice pairs to the minimal budget meeting
                   ``target_error`` (or drop the last anti-diagonal).
    pair_policy:   "full" | "diagonal" | "budget:N" explicit truncation.
    shard_axis:    mesh axis to k-shard over (``parallel.ozaki_shard``).
    comm:          "f64" | "int8" — what sharded calls move over the
                   interconnect: f64 operand words (GSPMD baseline) or
                   the packed int8-slice representation + exact int32
                   partials (``|comm=int8``; ~8x fewer bytes on k-shard
                   layouts, bitwise-identical results).
    plan_cache:    path of a persistent ``core.autotune.PlanCache`` —
                   tuned launch plans (result-invariant fields only) are
                   applied to matching shapes.
    autotune:      measure candidate plans on cache misses (consumed by
                   the serving pre-warm and the benchmark machinery; the
                   ``matmul`` hot path itself only ever *reads* a cache).
    """

    scheme: str = "ozaki_fp64"
    backend: str = "xla"
    num_splits: Optional[int] = None
    fuse_epilogue: bool = False
    streaming: bool = False
    target_error: Optional[float] = None
    fast_mode: bool = False
    pair_policy: str = "full"
    shard_axis: Optional[str] = None
    comm: str = "f64"
    plan_cache: Optional[str] = None
    autotune: bool = False

    def __post_init__(self):
        from repro.core.tuning import BACKENDS, COMM_MODES
        if self.scheme not in SCHEMES:
            raise ValueError(f"unknown scheme {self.scheme!r}; expected "
                             f"one of {SCHEMES}")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; expected "
                             f"one of {BACKENDS}")
        if self.comm not in COMM_MODES:
            raise ValueError(f"unknown comm {self.comm!r}; expected one "
                             f"of {COMM_MODES}")
        if self.num_splits is not None and self.num_splits < 1:
            raise ValueError(f"num_splits must be >= 1, got "
                             f"{self.num_splits}")
        if self.target_error is not None and not self.target_error > 0.0:
            raise ValueError(f"target_error must be > 0, got "
                             f"{self.target_error}")
        if self.streaming and self.fuse_epilogue:
            raise ValueError(
                "streaming and fuse_epilogue are mutually exclusive: "
                "streaming subsumes the epilogue fusion (pick one of "
                "'+streaming' / '+epilogue')")
        _validate_pair_policy(self.pair_policy)
        if self.scheme == "ozaki2_fp64":
            # Scheme II shares the backend/fusion/transport/accuracy/
            # cache knobs ('+epilogue' is the fused-CRT kernel, |shard=/
            # |comm=int8 the residue-wire transport); what it rejects is
            # the Scheme I pair machinery (no pair schedule to truncate —
            # accuracy scales via the mantissa budget) and streaming.
            # ``num_splits`` IS meaningful: it pins the residue modulus
            # count (the ``ozaki2-fp64xL`` accuracy dial).
            for field, why in _OZAKI2_REJECTED.items():
                if getattr(self, field) != _ozaki_only_fields()[field]:
                    raise ValueError(
                        f"{field}={getattr(self, field)!r} does not apply "
                        f"to scheme 'ozaki2-fp64': {why}")
        elif self.scheme != "ozaki_fp64":
            for field, default in _ozaki_only_fields().items():
                if getattr(self, field) != default:
                    raise ValueError(
                        f"{field}={getattr(self, field)!r} only applies to "
                        f"scheme 'ozaki-fp64', not {self.spec()!r}")

    # ---- string spec ---------------------------------------------------
    def spec(self) -> str:
        """Canonical compact spec; ``parse(p.spec()) == p`` always."""
        s = self.scheme.replace("_", "-")
        if self.num_splits is not None:
            s += f"x{self.num_splits}"
        if self.target_error is not None:
            s += f"@{self.target_error!r}"
        modes = (["fast"] if self.fast_mode else []) + \
            ([self.pair_policy] if self.pair_policy != "full" else [])
        if modes:
            s += ":" + ",".join(modes)
        if self.backend != "xla" or self.fuse_epilogue or self.streaming:
            s += "/" + self.backend + \
                ("+epilogue" if self.fuse_epilogue else "") + \
                ("+streaming" if self.streaming else "")
        if self.shard_axis:
            s += f"|shard={self.shard_axis}"
        if self.comm != "f64":
            s += f"|comm={self.comm}"
        if self.plan_cache:
            s += f"|cache={self.plan_cache}"
        if self.autotune:
            s += "|autotune"
        return s

    def __str__(self) -> str:
        return self.spec()

    @classmethod
    def parse(cls, spec: str) -> "MatmulPolicy":
        return _parse_spec(spec)

    @classmethod
    def of(cls, value) -> "MatmulPolicy":
        """Coerce a policy, a spec string, or None (-> ambient/default)."""
        if value is None:
            return default_policy()
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls.parse(value)
        raise TypeError(f"expected MatmulPolicy, spec str, or None; got "
                        f"{type(value).__name__}")

    # ---- JSON ----------------------------------------------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "MatmulPolicy":
        return cls(**d)

    # ---- interop -------------------------------------------------------
    def resolve_num_splits(self, k: int) -> int:
        """The split count this policy runs at for reduction extent k."""
        if self.num_splits is not None:
            return self.num_splits
        from repro.core.tuning import select_num_splits
        return select_num_splits(k)

    def ozaki_config(self, k: int, *, accum: str = "f64",
                     interpret: Optional[bool] = None):
        """The ``core.ozaki.OzakiConfig`` this policy resolves to.

        Shape-only (k sizes the auto split count), so the result is
        trace-stable. ``interpret=None`` takes the host default
        (``kernels.ops.INTERPRET``: interpret-mode Pallas off a TPU,
        Mosaic lowering on one) when the plan is built.
        """
        if self.scheme != "ozaki_fp64":
            raise ValueError(f"scheme {self.scheme!r} has no OzakiConfig")
        from repro.core.ozaki import OzakiConfig
        return OzakiConfig(
            num_splits=self.resolve_num_splits(k), accum=accum,
            backend=self.backend, fuse_epilogue=self.fuse_epilogue,
            streaming=self.streaming,
            pair_policy=self.pair_policy, target_error=self.target_error,
            fast_mode=self.fast_mode, shard_axis=self.shard_axis,
            comm=self.comm, fuse_diagonals=True, interpret=interpret)

    def modular_config(self, *, interpret: Optional[bool] = None):
        """The ``core.modular.ModularConfig`` this policy resolves to
        (Scheme II). ``num_splits`` maps onto the residue modulus count
        (the ``ozaki2-fp64xL`` spec dial); ``target_error`` sizes the
        mantissa budget via the guaranteed bound."""
        if self.scheme != "ozaki2_fp64":
            raise ValueError(f"scheme {self.scheme!r} has no ModularConfig")
        from repro.core.modular import ModularConfig
        return ModularConfig(num_moduli=self.num_splits,
                             target_error=self.target_error,
                             backend=self.backend,
                             fuse_epilogue=self.fuse_epilogue,
                             interpret=interpret)


# MatmulPolicy fields Scheme II rejects, with the reason (the rest —
# backend, fuse_epilogue (the fused-CRT kernel), shard_axis/comm (the
# residue-wire transport), num_splits, target_error, plan_cache,
# autotune — carry over).
_OZAKI2_REJECTED = {
    "streaming": "no residue streaming kernel (the fused-CRT '+epilogue' "
                 "route is the Scheme II fusion)",
    "fast_mode": "no pair schedule to truncate (use target_error or a "
                 "pinned modulus count xL instead)",
    "pair_policy": "no pair schedule to truncate (use target_error or a "
                   "pinned modulus count xL instead)",
}


@functools.lru_cache(maxsize=1)
def _ozaki_only_fields() -> dict:
    """Every MatmulPolicy field but ``scheme`` is ozaki-only, with its
    dataclass default as the neutral value a non-ozaki scheme must keep.
    Derived from the dataclass itself so a future field cannot be
    silently forgotten here."""
    return {f.name: f.default for f in dataclasses.fields(MatmulPolicy)
            if f.name != "scheme"}


def _validate_pair_policy(policy: str) -> None:
    """Syntactic pair-policy check (the schedule-level semantic check
    lives in ``core.tuning.parse_pair_policy``, which needs a split
    count)."""
    if policy in ("full", "diagonal"):
        return
    if policy.startswith("budget:"):
        tail = policy[len("budget:"):]
        if tail.isdigit() and int(tail) >= 1:
            return
        raise ValueError(f"pair budget must be a positive int, got "
                         f"{policy!r}")
    raise ValueError(f"unknown pair_policy {policy!r}; expected 'full', "
                     f"'diagonal', or 'budget:N'")


@functools.lru_cache(maxsize=256)
def _parse_spec(spec: str) -> MatmulPolicy:
    if not isinstance(spec, str) or not spec.strip():
        raise ValueError(f"empty policy spec {spec!r}")
    parts = spec.strip().split("|")
    core, opts = parts[0], parts[1:]

    kw: dict = {}
    for opt in opts:
        if opt == "autotune":
            kw["autotune"] = True
        elif opt.startswith("shard="):
            kw["shard_axis"] = opt[len("shard="):] or None
        elif opt.startswith("comm="):
            kw["comm"] = opt[len("comm="):]
        elif opt.startswith("cache="):
            kw["plan_cache"] = opt[len("cache="):] or None
        else:
            raise ValueError(f"unknown policy option {opt!r} in {spec!r}; "
                             f"expected shard=AXIS, comm=MODE, cache=PATH, "
                             f"autotune")

    if "/" in core:
        core, backend = core.split("/", 1)
        if backend.endswith("+streaming"):
            kw["streaming"] = True
            backend = backend[: -len("+streaming")]
        if backend.endswith("+epilogue"):
            kw["fuse_epilogue"] = True
            backend = backend[: -len("+epilogue")]
        kw["backend"] = _canon_backend(backend)
    if ":" in core:
        core, modes = core.split(":", 1)
        for mode in modes.split(","):
            if mode == "fast":
                kw["fast_mode"] = True
            elif mode in ("full", "diagonal") or mode.startswith("budget:"):
                if "pair_policy" in kw and mode != kw["pair_policy"]:
                    raise ValueError(f"conflicting pair policies in "
                                     f"{spec!r}")
                kw["pair_policy"] = mode
            else:
                raise ValueError(f"unknown mode {mode!r} in {spec!r}; "
                                 f"expected fast, full, diagonal, budget:N")
    if "@" in core:
        core, target = core.split("@", 1)
        try:
            kw["target_error"] = float(target)
        except ValueError:
            raise ValueError(f"malformed target_error {target!r} in "
                             f"{spec!r}") from None
    m = _SCHEME_RE.match(core)
    if not m:
        raise ValueError(f"malformed scheme {core!r} in {spec!r}")
    kw["scheme"] = _canon_scheme(m.group("scheme"))
    if m.group("splits") is not None:
        kw["num_splits"] = int(m.group("splits"))
    return MatmulPolicy(**kw)          # __post_init__ validates the rest


# ----------------------------------------------------------------------------
# Ambient default policy (mirrors jax.default_matmul_precision)
# ----------------------------------------------------------------------------

# thread-local like jax.default_matmul_precision: a scope entered on one
# thread must not leak into another thread's unscoped matmul calls
_DEFAULT_POLICY = threading.local()
_PACKAGE_DEFAULT = "ozaki_fp64"


def default_policy() -> MatmulPolicy:
    """The policy ``matmul`` runs under when none is passed: the innermost
    ``default_matmul_precision`` scope (on this thread), else the package
    default (the paper's FP64-accurate scheme, auto operating point)."""
    pol = getattr(_DEFAULT_POLICY, "value", None)
    if pol is not None:
        return pol
    return MatmulPolicy(scheme=_PACKAGE_DEFAULT)


@contextlib.contextmanager
def default_matmul_precision(precision):
    """Scope the ambient matmul policy (and its plan cache) — the repro
    counterpart of ``jax.default_matmul_precision``::

        with repro.default_matmul_precision("ozaki-fp64@1e-25:fast"):
            c = repro.matmul(a, b)          # runs under the scoped policy

    When the policy names a plan cache (``|cache=PATH``), the cache is
    loaded (memoized per path, reloaded on file change) and registered
    as the ambient ``core.autotune`` plan cache for the scope —
    subsuming a manual ``use_plan_cache`` — so both ``repro.matmul`` and
    traced model steps pick tuned launch plans up without any extra
    plumbing.

    The POLICY scope is thread-local (like
    ``jax.default_matmul_precision``); the plan-cache registry it feeds
    is the pre-existing process-global ``core.autotune`` slot, shared
    with the serving engine's tick scope. Cached plans are
    result-invariant by contract, so a cross-thread cache sighting can
    only change launch parameters, never results.
    """
    pol = MatmulPolicy.of(precision)
    cache_ctx = contextlib.nullcontext()
    if pol.plan_cache is not None:
        from repro.core.autotune import use_plan_cache
        cache_ctx = use_plan_cache(_load_plan_cache(pol.plan_cache))
    prev = getattr(_DEFAULT_POLICY, "value", None)
    _DEFAULT_POLICY.value = pol
    try:
        with cache_ctx:
            yield pol
    finally:
        _DEFAULT_POLICY.value = prev


# path -> (mtime, PlanCache), LRU-bounded: a serving process cycling
# through many per-model cache paths must not grow this without limit,
# and concurrent matmul callers (the engine is threaded) must not race
# the check-then-insert. Mutated only under _PLAN_CACHE_LOCK.
_PLAN_CACHE_MEMO: collections.OrderedDict = collections.OrderedDict()
_PLAN_CACHE_MEMO_MAX = 16
_PLAN_CACHE_LOCK = threading.Lock()


def _load_plan_cache(path: str):
    """The persistent PlanCache a policy names, memoized per path but
    re-loaded whenever the backing file changes on disk — an engine
    pre-warm or ``--autotune`` run persisting new plans mid-process must
    not leave later ``matmul`` calls reading a stale snapshot."""
    from repro.core.autotune import PlanCache
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        mtime = None
    with _PLAN_CACHE_LOCK:
        hit = _PLAN_CACHE_MEMO.get(path)
        if hit is not None and hit[0] == mtime:
            _PLAN_CACHE_MEMO.move_to_end(path)
            return hit[1]
    # load outside the lock: file I/O + JSON parse must not serialize
    # every other thread's memo hits behind it
    cache = PlanCache.load(path)
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE_MEMO[path] = (mtime, cache)
        _PLAN_CACHE_MEMO.move_to_end(path)
        while len(_PLAN_CACHE_MEMO) > _PLAN_CACHE_MEMO_MAX:
            _PLAN_CACHE_MEMO.popitem(last=False)
    return cache


def _active_plan_cache(pol: MatmulPolicy):
    """The cache ``matmul`` reads tuned plans from: the ambient registry
    first (an engine tick / default_matmul_precision scope), else the
    policy's own cache path."""
    from repro.core.autotune import active_plan_cache
    cache = active_plan_cache()
    if cache is None and pol.plan_cache is not None:
        cache = _load_plan_cache(pol.plan_cache)
    return cache


def _apply_tuned_plan(cfg, cache, *, m: int, n: int, k: int, batch: int):
    """Fold a cached tuned plan into an OzakiConfig — RESULT-INVARIANT
    fields only (tile shapes + the stages/epilogue/streaming fusion flip,
    all bitwise-neutral per the backend-parity suite), so a cached plan
    can never change what ``matmul`` returns, only how fast it runs."""
    if cache is None:
        return cfg
    from repro.core.autotune import plan_cache_key
    dtype = "float64" if cfg.accum == "f64" else "float32"
    plan = cache.get(plan_cache_key(m, n, k, batch=batch, dtype=dtype,
                                    backend=cfg.backend))
    if plan is None:
        return cfg
    return dataclasses.replace(cfg, tile=plan.tile,
                               fuse_epilogue=(plan.fusion == "epilogue"),
                               streaming=(plan.fusion == "streaming"))


# ----------------------------------------------------------------------------
# The front door
# ----------------------------------------------------------------------------

def matmul(a, b, precision=None):
    """``a @ b`` under a precision policy — the package's one entry point.

    precision: a ``MatmulPolicy``, a spec string (``"ozaki-fp64x9"``,
    ``"bf16"``, ...), or None for the ambient default
    (``default_matmul_precision`` scope, else the paper scheme at the
    auto operating point).

    Dispatch (ozaki scheme) on rank/dtype/DW-ness, to the
    bitwise-verified pipelines:

    * ``DW`` operands          -> the TPU-native df32 entry (df32 out);
                                  a 3-D ``a`` with 2-D weights folds
                                  its batch into rows.
    * complex 2-D              -> the 4-mul complex pipeline.
    * 3-D ``a``                -> the batched pipeline; ``b`` may be 3-D
                                  (stacked weights, batch-grid kernel) or
                                  2-D (broadcast weights, rows fold).
    * 2-D f64                  -> the paper path (f64 out).
    * 2-D f32                  -> the df32 pipeline (f32 out) — runs
                                  entirely in {int8, int32, f32}.

    ``b`` is always taken in natural ``(..., k, n)`` orientation — the
    front door transposes for the entries that want ``B^T`` (exact).
    """
    tracing.count("matmul_traces")
    with tracing.span(tracing.MATMUL), tracing.scope(tracing.MATMUL):
        pol = MatmulPolicy.of(precision)
        if pol.scheme == "bf16":
            return _matmul_bf16(a, b)
        if pol.scheme == "int8_quant":
            return _matmul_int8_quant(a, b)
        if pol.scheme == "ozaki2_fp64":
            return _matmul_ozaki2(a, b, pol)
        return _matmul_ozaki_dispatch(a, b, pol)


def _matmul_bf16(a, b):
    """The TPU-native baseline: bf16 operands, f32 accumulation. 2-D
    weights share ``models.layers``' definition of the baseline (one
    source of truth); a stacked 3-D ``b`` needs batched-matmul
    semantics, which the layers projection never has."""
    import jax.numpy as jnp
    if getattr(b, "ndim", 2) == 2:
        from repro.models.layers import _matmul_bf16 as impl
        return impl(a, b, jnp.bfloat16)
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _matmul_int8_quant(a, b):
    """Lossy per-channel int8 quantization (what IMMUs were built for)."""
    if getattr(b, "ndim", 2) != 2:
        raise ValueError("int8-quant expects 2-D weights (k, n); got "
                         f"{getattr(b, 'shape', None)}")
    from repro.models.layers import _matmul_int8_quant as impl
    import jax.numpy as jnp
    return impl(a.astype(jnp.float32), b.astype(jnp.float32))


def _apply_tuned_modular_plan(cfg, cache, *, m: int, n: int, k: int,
                              batch: int):
    """Fold a cached Scheme II tuned plan into a ModularConfig — tile
    shapes and the stages<->epilogue fusion flip only (result-invariant:
    the residue GEMMs are exact integer arithmetic under any tiling and
    the fused-CRT epilogue replays the reference rounding sequence)."""
    if cache is None:
        return cfg
    from repro.core.autotune import plan_cache_key
    plan = cache.get(plan_cache_key(m, n, k, batch=batch, dtype="float64",
                                    accum="f64", backend=cfg.backend,
                                    scheme="ozaki2_fp64"))
    if plan is None or getattr(plan, "scheme", "ozaki_fp64") != \
            "ozaki2_fp64":
        return cfg
    return dataclasses.replace(cfg, tile=plan.tile,
                               fuse_epilogue=(plan.fusion == "epilogue"))


def _matmul_ozaki2(a, b, pol: MatmulPolicy):
    """Scheme II dispatch: residue-system int8 GEMMs + CRT.

    float64 is the native route; complex128 decomposes into three or
    four real residue GEMMs (``ozaki2_matmul_complex``) and float32
    reconstructs through the double-float32 CRT target
    (``ozaki2_matmul_df32``). DW operands raise — the Scheme I DW
    pipeline is a different algorithm than the policy named.
    """
    import jax.numpy as jnp

    from repro.core.modular import (ozaki2_matmul, ozaki2_matmul_batched,
                                    ozaki2_matmul_complex,
                                    ozaki2_matmul_df32)
    from repro.core.xmath import DW

    if isinstance(a, DW) or isinstance(b, DW):
        raise TypeError("ozaki2-fp64 has no DW path (the CRT "
                        "reconstruction is FP64); use scheme 'ozaki-fp64'")
    if jnp.issubdtype(a.dtype, jnp.complexfloating) or \
            jnp.issubdtype(b.dtype, jnp.complexfloating):
        if a.ndim != 2 or b.ndim != 2:
            raise ValueError(f"complex operands must be 2-D, got "
                             f"{a.shape} @ {b.shape}")
        return ozaki2_matmul_complex(a, b, pol.modular_config())
    if a.dtype != b.dtype:
        raise TypeError(f"dtype mismatch: {a.dtype} @ {b.dtype}")
    if a.dtype == jnp.float32:
        if a.ndim != 2 or b.ndim != 2:
            raise ValueError(f"float32 Scheme II operands must be 2-D, "
                             f"got {a.shape} @ {b.shape}")
        return ozaki2_matmul_df32(a, b, pol.modular_config())
    if a.dtype != jnp.float64:
        raise TypeError(f"ozaki2-fp64 runs on float64/float32/complex128 "
                        f"operands, got {a.dtype}")
    cfg = pol.modular_config()
    cache = _active_plan_cache(pol)
    if a.ndim == 3:
        bsz, m, k = a.shape
        cfg = _apply_tuned_modular_plan(cfg, cache, m=m, n=b.shape[-1],
                                        k=k, batch=bsz)
        return ozaki2_matmul_batched(a, b, cfg)
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"matmul expects 2-D or 3-D operands, got "
                         f"{a.shape} @ {b.shape}")
    m, k = a.shape
    if pol.shard_axis:
        from repro.parallel.ozaki_shard import (active_shard_mesh,
                                                constrain_batched_kshard,
                                                distributed_ozaki2_matmul)
        mesh = active_shard_mesh()
        if pol.comm == "int8" and mesh is not None and \
                pol.shard_axis in mesh.axis_names and \
                k % mesh.shape[pol.shard_axis] == 0:
            # |comm=int8: the residue-wire collective schedule — exact
            # int32 psum/reduce-scatter of the per-modulus residue
            # partials, bitwise-identical to the unsharded reference
            # for any mesh shape.
            return distributed_ozaki2_matmul(a, b, mesh, cfg,
                                             axis=pol.shard_axis)
        # mirror Scheme I's composition point: pin the reduction dim to
        # the registered shard mesh; silently a no-op without a mesh.
        a, b = constrain_batched_kshard(a, b, pol.shard_axis)
    cfg = _apply_tuned_modular_plan(cfg, cache, m=m, n=b.shape[1], k=k,
                                    batch=1)
    return ozaki2_matmul(a, b, cfg)


def _matmul_ozaki_dispatch(a, b, pol: MatmulPolicy):
    import jax.numpy as jnp

    from repro.core.ozaki import (ozaki_matmul, ozaki_matmul_batched,
                                  ozaki_matmul_complex, ozaki_matmul_dw)
    from repro.core.xmath import DW

    if isinstance(a, DW) or isinstance(b, DW):
        if not (isinstance(a, DW) and isinstance(b, DW)):
            raise TypeError("DW matmul needs both operands as DW")
        if a.hi.ndim == 3 and b.hi.ndim == 2:
            # broadcast weights: fold the batch into rows — exact, the
            # same fold the f32/f64 batched pipeline makes
            bsz, m, k = a.hi.shape
            with tracing.scope(tracing.LAYOUT):
                a = DW(a.hi.reshape(bsz * m, k), a.lo.reshape(bsz * m, k))
            out = _matmul_ozaki_dispatch(a, b, pol)
            with tracing.scope(tracing.LAYOUT):
                return DW(out.hi.reshape(bsz, m, -1),
                          out.lo.reshape(bsz, m, -1))
        if a.hi.ndim != 2 or b.hi.ndim != 2:
            raise ValueError(f"DW operands must be 2-D, or 3-D with 2-D "
                             f"weights, got {a.hi.shape} @ {b.hi.shape}")
        k = a.hi.shape[-1]
        cfg = pol.ozaki_config(k, accum="df32")
        with tracing.scope(tracing.LAYOUT):
            b_t = DW(b.hi.T, b.lo.T)           # exact: a permutation
        cfg = _apply_tuned_plan(cfg, _active_plan_cache(pol),
                                m=a.hi.shape[0], n=b.hi.shape[1], k=k,
                                batch=1)
        return ozaki_matmul_dw(a, b_t, cfg)

    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        if a.ndim != 2 or b.ndim != 2:
            raise ValueError(f"complex operands must be 2-D, got "
                             f"{a.shape} @ {b.shape}")
        cfg = pol.ozaki_config(a.shape[1], accum="f64")
        return ozaki_matmul_complex(a, b, cfg)

    # the front door validates what the internal entry points assumed:
    # matching float operands (accuracy silently degrading to the f32
    # pipeline because ONE operand was f32 is exactly the surprise a
    # precision-policy API exists to prevent)
    if a.dtype != b.dtype:
        raise TypeError(f"dtype mismatch: {a.dtype} @ {b.dtype}")
    if a.dtype not in (jnp.float32, jnp.float64):
        raise TypeError(f"matmul supports float32/float64/complex128/DW "
                        f"operands, got {a.dtype}")

    if a.ndim == 3:
        # shard_axis on the batched path: structural no-op, exactly like
        # models/layers (in-scan 3-D constraints trip an XLA SPMD bug on
        # the pinned jax — see ROADMAP; sharded batched GEMMs are served
        # by parallel.ozaki_shard.ozaki_matmul_kshard_auto).
        bsz, m, k = a.shape
        accum = "f64" if a.dtype == jnp.float64 else "df32"
        cfg = pol.ozaki_config(k, accum=accum)
        cfg = _apply_tuned_plan(cfg, _active_plan_cache(pol),
                                m=m, n=b.shape[-1], k=k, batch=bsz)
        return ozaki_matmul_batched(a, b, cfg)

    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"matmul expects 2-D or 3-D operands, got "
                         f"{a.shape} @ {b.shape}")
    m, k = a.shape
    n = b.shape[-1]
    if pol.shard_axis:
        from repro.parallel.ozaki_shard import (active_shard_mesh,
                                                constrain_batched_kshard,
                                                distributed_ozaki_matmul)
        mesh = active_shard_mesh()
        if pol.comm == "int8" and mesh is not None and \
                pol.shard_axis in mesh.axis_names and \
                a.dtype == jnp.float64 and \
                k % mesh.shape[pol.shard_axis] == 0:
            # |comm=int8: run the explicit int8-slice collective
            # schedule instead of GSPMD f64-operand sharding — exact
            # int32 psum of the pair partials, bitwise-identical to the
            # unsharded reference for any mesh shape.
            cfg = pol.ozaki_config(k, accum="f64")
            return distributed_ozaki_matmul(a, b, mesh, cfg,
                                            axis=pol.shard_axis)
        # same composition point as models/layers: pin the reduction dim
        # to the registered shard mesh on plain 2-D calls (the path
        # verified bitwise-safe); silently a no-op without a mesh.
        a, b = constrain_batched_kshard(a, b, pol.shard_axis)
    cache = _active_plan_cache(pol)
    if a.dtype == jnp.float64:
        cfg = _apply_tuned_plan(pol.ozaki_config(k, accum="f64"), cache,
                                m=m, n=n, k=k, batch=1)
        return ozaki_matmul(a, b, cfg)
    # f32: the TPU-native df32 pipeline ({int8, int32, f32} only)
    from repro.core.xmath import dw_to_single
    cfg = _apply_tuned_plan(pol.ozaki_config(k, accum="df32"), cache,
                            m=m, n=n, k=k, batch=1)
    with tracing.scope(tracing.LAYOUT):
        a, b_t = DW(a, jnp.zeros_like(a)), DW(b.T, jnp.zeros_like(b.T))
    out = ozaki_matmul_dw(a, b_t, cfg)
    with tracing.scope(tracing.SCALE_OUT):
        return dw_to_single(out)


# ----------------------------------------------------------------------------
# Legacy-config interop (ArchConfig's ozaki_* fields, engine kwargs)
# ----------------------------------------------------------------------------

def policy_from_legacy_fields(cfg, scheme: Optional[str] = None
                              ) -> MatmulPolicy:
    """Derive a MatmulPolicy from legacy ``ozaki_*``-style fields
    (duck-typed: missing fields take their legacy defaults). Non-ozaki
    schemes drop the ozaki knobs — they configure nothing there.
    ``scheme`` overrides ``cfg.matmul_precision`` (the legacy engine
    kwarg semantics: switching scheme keeps the config's ozaki knobs)."""
    if scheme is None:
        scheme = getattr(cfg, "matmul_precision", "ozaki_fp64")
    if scheme != "ozaki_fp64":
        return MatmulPolicy(scheme=scheme)
    return MatmulPolicy(
        scheme="ozaki_fp64",
        backend=getattr(cfg, "ozaki_backend", "xla"),
        num_splits=getattr(cfg, "ozaki_splits", 9),
        fuse_epilogue=getattr(cfg, "ozaki_fuse_epilogue", False),
        target_error=getattr(cfg, "ozaki_target_error", 0.0) or None,
        fast_mode=getattr(cfg, "ozaki_fast_mode", False),
        shard_axis=getattr(cfg, "ozaki_shard_axis", "") or None,
        plan_cache=getattr(cfg, "ozaki_plan_cache", "") or None,
        autotune=getattr(cfg, "ozaki_autotune", False))


def policy_of(cfg) -> MatmulPolicy:
    """The MatmulPolicy a config-like object resolves to: its
    ``matmul_policy`` spec when set, else the legacy-field derivation."""
    spec = getattr(cfg, "matmul_policy", "")
    if spec:
        return MatmulPolicy.parse(spec)
    return policy_from_legacy_fields(cfg)


# names the legacy serving-engine kwargs carry -> policy fields ("" and
# 0.0 are the legacy "unset" spellings for shard_axis / target_error)
_LEGACY_OVERRIDE_FIELDS = {
    "ozaki_backend": ("backend", lambda v: v),
    "ozaki_fuse_epilogue": ("fuse_epilogue", lambda v: v),
    "ozaki_shard_axis": ("shard_axis", lambda v: v or None),
    "ozaki_target_error": ("target_error", lambda v: v or None),
    "ozaki_fast_mode": ("fast_mode", lambda v: v),
}


def merge_legacy_overrides(cfg, overrides: dict) -> MatmulPolicy:
    """Apply legacy per-knob override kwargs on top of a config's
    resolved policy, as ONE merged policy.

    This preserves spec-only knobs the legacy fields cannot express
    (``pair_policy``, an auto split count, a plan-cache path carried in
    the spec): ``ServingEngine(cfg_with_policy, ozaki_fast_mode=True)``
    keeps the config's policy and flips only ``fast_mode``, instead of
    discarding the spec. A ``matmul_precision`` override switches the
    scheme; switching ONTO ozaki seeds the ozaki knobs from the config's
    legacy fields (the pre-policy engine semantics)."""
    pol = policy_of(cfg)
    scheme = overrides.get("matmul_precision", pol.scheme)
    if scheme != "ozaki_fp64":
        return MatmulPolicy(scheme=scheme)
    if pol.scheme != "ozaki_fp64":
        pol = policy_from_legacy_fields(cfg, scheme="ozaki_fp64")
    kw = {field: conv(overrides[name])
          for name, (field, conv) in _LEGACY_OVERRIDE_FIELDS.items()
          if name in overrides}
    return dataclasses.replace(pol, **kw) if kw else pol
