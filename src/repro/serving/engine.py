"""Serving: batched prefill/decode with a continuous-batching scheduler.

Slot-based continuous batching (vLLM-style, adapted to static JAX
shapes): the engine owns ONE batched ``DecodeState`` with ``num_slots``
rows, each with an independent cursor (``DecodeState.pos`` is a (b,)
vector). Admission prefillis a request on a batch-1 state and inserts
its caches into a free slot; every engine tick decodes ALL slots in one
jitted step (idle slots compute masked garbage — the static-shape tax).
Finished rows free their slot immediately, so new requests join mid-
flight without draining the batch.

Matmul precision: the engine takes ONE ``policy`` per deployment — a
``repro.api.MatmulPolicy`` (or spec string like
``"ozaki-fp64@1e-25:fast/pallas_fused+epilogue"``) that overrides the
model config's matmul policy wholesale (e.g. serve an FP64-accurate
variant of a checkpoint without a new config). The pre-PR-5 per-knob
kwargs (``matmul_precision`` / ``ozaki_backend`` / ... ) still work for
legacy callers but cannot be mixed with ``policy``. With
``matmul_precision="ozaki_fp64"`` every dense projection in the batched
decode step is a ``(num_slots, 1, k) @ (k, n)`` matmul against shared
weights — exactly ``ozaki_matmul_batched``'s broadcast-weights case, so
the whole batch shares one set of slice GEMMs per projection
(``models.layers._matmul_ozaki`` routes 3-D activations there).
``ozaki_fuse_epilogue`` selects the epilogue-fused GEMM+accumulate
kernels; ``ozaki_shard_axis`` (+ a ``mesh``) wires k-sharding for the
Ozaki projections — the engine scopes its mesh into
``parallel.ozaki_shard``'s registry around every tick, so traced model
steps pick it up without leaking it to other engines. (On the pinned
jax version the in-model constraints engage only for 2-D projections —
see ``models.layers._matmul_ozaki`` for the XLA SPMD caveat; the
sharded batched GEMM itself is served by
``parallel.ozaki_shard.ozaki_matmul_kshard_auto``.)

Plan cache pre-warm: with a ``plan_cache`` (or ``cfg.ozaki_plan_cache``
path) and ``matmul_precision="ozaki_fp64"``, the engine resolves a
``PipelinePlan`` for every dense decode projection shape AT STARTUP —
measured on the live backend when ``autotune_plans`` /
``cfg.ozaki_autotune`` is set, analytic otherwise — and persists the
cache. The cache is then scoped around every tick
(``core.autotune.use_plan_cache``) exactly like the mesh, so the first
traced decode step picks the tuned launch parameters up from the cache:
steady-state serving never tunes (or even re-plans) on the request
path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, init_decode_state, prefill
from repro.models.transformer import DecodeState


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int
    eos_id: Optional[int] = None
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def ozaki_projection_shapes(cfg) -> list[tuple[int, int]]:
    """Distinct (k, n) weight shapes of the dense decode projections.

    These are the ``(slots, 1, k) @ (k, n)`` broadcast-weights matmuls a
    decode tick issues through ``models.layers.policy_matmul``: the
    attention q/k/v/o projections, the fused gate+up and the down MLP
    matmuls, and the unembedding. MoE expert matmuls run per-expert with
    the same (d, 2*ff_e)/(ff_e, d) pattern when configured; SSM inner
    projections are left to miss into the analytic plan (cheap).
    """
    d = cfg.d_model
    shapes = set()
    if cfg.num_heads:
        h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        shapes |= {(d, h * hd), (d, kv * hd), (h * hd, d)}
    if cfg.d_ff:
        shapes |= {(d, 2 * cfg.d_ff), (cfg.d_ff, d)}
    if getattr(cfg, "moe", None) is not None:
        ffe = cfg.moe.d_ff_expert
        shapes |= {(d, 2 * ffe), (ffe, d)}
    shapes.add((d, cfg.vocab_size))          # unembed (tied: embed.T)
    return sorted(shapes)


def _insert_row(batched, single, row: int):
    """Write a batch-1 state pytree into slot ``row`` of the batched one.

    Every DecodeState leaf has batch at dim 1 (stacked (L, b, ...)),
    except ``pos`` (dim 0).
    """
    def ins(full, one):
        if full.ndim == 1:                       # pos vector (b,)
            return full.at[row].set(one[0] if one.ndim else one)
        return jax.lax.dynamic_update_slice(
            full, one.astype(full.dtype),
            (0, row) + (0,) * (full.ndim - 2))

    return jax.tree.map(ins, batched, single)


class ServingEngine:
    def __init__(self, cfg, params, *, num_slots: int = 4,
                 max_len: int = 256, cache_dtype=jnp.float32,
                 sample_fn: Callable = greedy_sample,
                 policy=None,
                 matmul_precision: Optional[str] = None,
                 ozaki_backend: Optional[str] = None,
                 ozaki_fuse_epilogue: Optional[bool] = None,
                 ozaki_shard_axis: Optional[str] = None,
                 ozaki_target_error: Optional[float] = None,
                 ozaki_fast_mode: Optional[bool] = None,
                 mesh=None, plan_cache=None,
                 autotune_plans: Optional[bool] = None):
        # ONE policy object/spec replaces the six per-knob overrides: it
        # becomes the config's matmul_policy (authoritative — ArchConfig
        # back-fills matmul_precision and the legacy ozaki_* fields from
        # it, so every downstream reader agrees). The per-knob kwargs
        # stay for legacy callers but cannot be mixed with `policy`.
        overrides = {}
        if matmul_precision is not None:
            overrides["matmul_precision"] = matmul_precision
        if ozaki_backend is not None:
            overrides["ozaki_backend"] = ozaki_backend
        if ozaki_fuse_epilogue is not None:
            overrides["ozaki_fuse_epilogue"] = ozaki_fuse_epilogue
        if ozaki_shard_axis is not None:
            overrides["ozaki_shard_axis"] = ozaki_shard_axis
        if ozaki_target_error is not None:
            overrides["ozaki_target_error"] = ozaki_target_error
        if ozaki_fast_mode is not None:
            overrides["ozaki_fast_mode"] = ozaki_fast_mode
        if policy is not None:
            if overrides:
                raise ValueError(
                    "pass either policy=... or the legacy ozaki_*/"
                    f"matmul_precision kwargs, not both: {sorted(overrides)}")
            from repro.api import MatmulPolicy
            cfg = dataclasses.replace(
                cfg, matmul_policy=MatmulPolicy.of(policy).spec())
        elif overrides:
            # legacy kwargs merge INTO the config's resolved policy (one
            # spec stays authoritative), so spec-only knobs the legacy
            # fields can't express — pair_policy, auto split count — are
            # not silently discarded by a per-knob override.
            from repro.api import merge_legacy_overrides
            cfg = dataclasses.replace(
                cfg,
                matmul_policy=merge_legacy_overrides(cfg, overrides).spec())
        self.mesh = mesh
        self.cfg = cfg
        # plan cache: a PlanCache, a path, or the config's path; pre-warm
        # resolves every decode projection shape before serving starts.
        if plan_cache is None:
            plan_cache = getattr(cfg, "ozaki_plan_cache", "") or None
        if isinstance(plan_cache, (str, bytes)) or hasattr(plan_cache,
                                                           "__fspath__"):
            from repro.core.autotune import PlanCache
            plan_cache = PlanCache.load(plan_cache)
        self.plan_cache = plan_cache
        self.autotune_plans = (getattr(cfg, "ozaki_autotune", False)
                               if autotune_plans is None else autotune_plans)
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.sample_fn = sample_fn
        self.state = init_decode_state(cfg, num_slots, max_len,
                                       dtype=cache_dtype, per_row_pos=True)
        self.slot_req: list[Optional[Request]] = [None] * num_slots
        self.next_token = np.zeros((num_slots, 1), np.int32)
        self.waiting: list[Request] = []
        self.finished: list[Request] = []
        self.cache_dtype = cache_dtype
        self._decode = jax.jit(functools.partial(decode_step, cfg))
        self._steps = 0
        if (self.plan_cache is not None and
                cfg.matmul_precision == "ozaki_fp64"):
            self._prewarm_plans()            # before any request is served

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.waiting.append(req)

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self):
        for slot in self._free_slots():
            if not self.waiting:
                break
            req = self.waiting.pop(0)
            single = init_decode_state(self.cfg, 1, self.max_len,
                                       dtype=self.cache_dtype,
                                       per_row_pos=True)
            p = req.prompt[None, :]
            single = single._replace(pos=jnp.int32(0))
            single, last = prefill(self.cfg, self.params,
                                   {"tokens": jnp.asarray(p)}, single)
            single = single._replace(
                pos=jnp.full((1,), single.pos, jnp.int32))
            self.state = _insert_row(self.state, single, slot)
            first = np.asarray(self.sample_fn(last))[0]
            req.generated.append(int(first))
            self.next_token[slot, 0] = int(first)
            self.slot_req[slot] = req

    def _retire(self, slot: int):
        req = self.slot_req[slot]
        req.done = True
        self.finished.append(req)
        self.slot_req[slot] = None
        # neutralize the cursor so the idle row stays cheap/masked
        self.state = self.state._replace(
            pos=self.state.pos.at[slot].set(0))

    def _mesh_scope(self):
        """Scope this engine's mesh around traced model calls.

        The shard mesh is an ambient registry (``parallel.ozaki_shard``);
        scoping it per step — instead of registering it globally at
        construction — keeps two engines with different meshes (or a
        later mesh-less engine) from seeing each other's mesh. Without a
        mesh the ambient registration, if any, stays in effect.
        """
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel.ozaki_shard import use_shard_mesh
        return use_shard_mesh(self.mesh)

    def _plan_scope(self):
        """Scope this engine's plan cache around traced model calls, the
        same way ``_mesh_scope`` scopes the mesh: the jitted decode step
        reads it at trace time (``models.layers`` consults the ambient
        cache), so cached launch plans apply without re-planning — let
        alone re-tuning — on the request path."""
        if self.plan_cache is None:
            return contextlib.nullcontext()
        from repro.core.autotune import use_plan_cache
        return use_plan_cache(self.plan_cache)

    def _prewarm_plans(self):
        """Resolve a PipelinePlan for every decode projection shape.

        Runs at construction, BEFORE any request: with
        ``autotune_plans`` each cache miss is measured on the live
        backend (warm-up + ``block_until_ready``); without it the
        analytic plan is stored. Either way every steady-state decode
        projection is a cache HIT afterwards, and the cache file (when
        backed by a path) holds the plans for the next process.
        """
        from repro.api import policy_of
        from repro.core.autotune import plan_cache_key
        from repro.core.tuning import select_pipeline_plan
        cfg = self.cfg
        pol = policy_of(cfg)             # one object carries every knob
        for k, n in ozaki_projection_shapes(cfg):
            key = plan_cache_key(1, n, k, batch=self.num_slots,
                                 dtype="float32", backend=pol.backend)
            if key in self.plan_cache:
                self.plan_cache.get(key)         # count the hit
                continue
            plan = select_pipeline_plan(
                1, n, k, batch=self.num_slots, broadcast_weights=True,
                backend=pol.backend, accum="df32",
                num_splits=pol.num_splits,
                fuse_epilogue=pol.fuse_epilogue,
                target_error=pol.target_error, fast_mode=pol.fast_mode,
                dtype="float32", cache=self.plan_cache,
                autotune=self.autotune_plans)
            if key not in self.plan_cache:       # analytic miss: store it
                self.plan_cache.put(key, plan)
        if self.plan_cache.path is not None:
            self.plan_cache.save()

    # ------------------------------------------------------------------
    def step(self):
        """One engine tick: admit, one batched decode, retire."""
        with self._mesh_scope(), self._plan_scope():
            self._admit()
            if all(r is None for r in self.slot_req):
                return
            logits, self.state = self._decode(
                self.params, self.state, jnp.asarray(self.next_token))
        toks = np.asarray(self.sample_fn(logits))
        self._steps += 1
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            if len(req.generated) >= req.max_new_tokens or \
                    (req.eos_id is not None and
                     req.generated[-1] == req.eos_id) or \
                    int(self.state.pos[slot]) >= self.max_len:
                self._retire(slot)
                continue
            req.generated.append(int(toks[slot]))
            self.next_token[slot, 0] = int(toks[slot])

    def run_until_done(self, max_ticks: int = 10_000):
        while (self.waiting or
               any(r is not None for r in self.slot_req)):
            self.step()
            max_ticks -= 1
            if max_ticks <= 0:
                raise TimeoutError("serving engine did not drain")
        return self.finished


def generate_sequential(cfg, params, prompt: np.ndarray,
                        max_new_tokens: int, *, max_len: int = 256,
                        cache_dtype=jnp.float32,
                        sample_fn: Callable = greedy_sample) -> list[int]:
    """Single-request reference generator (the engine must match this)."""
    state = init_decode_state(cfg, 1, max_len, dtype=cache_dtype)
    state, last = prefill(cfg, params, {"tokens": jnp.asarray(prompt[None])},
                          state)
    out = [int(np.asarray(sample_fn(last))[0])]
    tok = jnp.asarray([[out[-1]]], jnp.int32)
    for _ in range(max_new_tokens - 1):
        logits, state = decode_step(cfg, params, state, tok)
        nxt = int(np.asarray(sample_fn(logits))[0])
        out.append(nxt)
        tok = jnp.asarray([[nxt]], jnp.int32)
    return out
