"""Distributed Ozaki GEMM — the paper's DGEMM scaled onto the mesh (O4).

Two sharded layouts, one invariant: every schedule below is **bitwise
identical** to the single-device reference — integer collectives are
associative, so the distributed sums reproduce the single-device rounding
stream exactly, for any mesh shape (the elasticity invariant used by the
checkpoint/restart tests).

**k-sharded** (``distributed_ozaki_matmul``): the reduction dimension is
sharded. Each device:

  1. contributes its local row/col maxima to a *global* ``pmax`` so all
     shards split against the same shared exponents (the Ozaki invariant:
     slices of one row live in one mantissa space);
  2. extracts int8 slices of its local k-chunk and runs the local slice
     GEMMs (int8 x int8 -> int32, exact);
  3. reduces each anti-diagonal's int32 partial product with an integer
     collective (``parallel.collectives``) — NO f64 operand ever crosses
     a link (the int8-slice transport, ``comm="int8"`` in the policy
     spec; ``core.tuning.comm_bytes_model`` prices it against the GSPMD
     f64-operand baseline);
  4. performs the high-precision scaled accumulation once, on the reduced
     products.

Exactness requires accumulator headroom for ``k_global`` terms (not just
the local chunk) plus diagonal-fusion slack — ``alpha`` is computed from
the GLOBAL k, mirroring Eq. (3) of the paper.

k-shard collective schedules:
  * ``schedule="psum"``      — stacked psum of all anti-diagonals at the
    end; result replicated over the k-axis (paper-faithful layout).
  * ``schedule="overlap"``   — diagonal d's psum is issued BEFORE diagonal
    d+1's GEMMs are built, so the int32 all-reduce rides the links while
    the next diagonal computes (compute/comm overlap; beyond-paper O4b).
  * ``schedule="reduce_scatter"`` — int32 reduce-scatter over the OUTPUT
    COLUMNS instead of an all-reduce: 2x less link traffic, and the
    high-precision accumulation runs on 1/P of the columns per chip.
    C comes out sharded (m@m_axis, n@axis) — the natural layout for a
    GEMM feeding the next sharded operator (beyond-paper O4c; §Perf).
  * ``schedule="rs_stream"`` — per-diagonal reduce-scatter issued as each
    diagonal's GEMMs finish (overlap + scatter combined).

**m/n-sharded** (``ozaki_matmul_mnshard``): A row-sharded, B
column-sharded, full k local. Instead of all-gathering B's f64 words,
each device splits its column block locally and all-gathers the packed
``SliceWire`` (int8 slice stack + int32 exponents,
``parallel.compression``) over a ``ring_all_gather`` — ``s`` bytes per
element instead of 8. The gathered representation feeds the plan's OWN
executor (``core.executors.get_executor``), so the result is
bitwise-identical to the unsharded pipeline for every backend by
construction. ``schedule="overlap"`` gathers B's slice planes one ring
hop chain per plane, issued just before the first anti-diagonal needing
the plane — plane q+1's hops overlap diagonal q's GEMMs.

**2-D (k x batch)** (``distributed_ozaki_matmul_batched``): the serving
layout from the SNIPPETS host-platform recipe — batch rows spread over
one mesh axis, the reduction over another; the batch folds into rows
locally (row-independent, exact) and the k-shard machinery above runs
unchanged.

**Scheme II** (``distributed_ozaki2_matmul`` / ``ozaki2_matmul_mnshard``):
the residue pipeline rides the same two layouts. k-shard: the residue
map is per-element in k, so each device's ``(ell, m, n)`` int32 residue
partials reduce with ONE stacked integer collective (``psum`` /
``reduce_scatter``) and the balanced-Garner CRT runs once on the reduced
stack — ``ell`` modulus planes cross the wire instead of Scheme I's
``s`` anti-diagonals. m/n-shard: the packed ``ResidueWire`` (int8
centered residues + int32 exponents, ``parallel.compression``) is
ring-all-gathered — ``ell`` bytes per element, beating the SliceWire's
``s`` exactly when ``ell < s``. Both are bitwise identical to the
single-device reference (the policy spec's
``ozaki2-fp64|shard=AXIS|comm=int8`` route).

Batched GSPMD composition: ``ozaki_matmul_kshard_auto`` accepts the
batched API's operand ranks ((B, m, k) activations with stacked or
broadcast weights) and records the axis on the config so the
``PipelinePlan`` carries it; ``cfg.comm="int8"`` re-routes it onto the
explicit int8-slice schedules above. ``constrain_batched_kshard`` + the
``set_shard_mesh`` / ``use_shard_mesh`` registry are the in-trace
composition points the model/serving layers use for
``ArchConfig.ozaki_shard_axis``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.executors import gemm_xla, get_executor, int32_to_dw
from repro.core.modular import (ModularConfig, center_mod, crt_digits,
                                crt_value, garner_constants,
                                residues_from_slices, usable_moduli)
from repro.core.ozaki import OzakiConfig, resolve_accuracy_config
from repro.core.splitting import SplitResult, row_exponents, split_int
from repro.core.xmath import DW, dw_add, ldexp, refuse_f64_on_tpu
from repro.parallel.collectives import (psum_exact_int32, reduce_scatter_sum,
                                        ring_all_gather)
from repro.parallel.compression import (ResidueWire, SliceWire, pack_residues,
                                        pack_slices, unpack_residues)

KSHARD_SCHEDULES = ("psum", "overlap", "reduce_scatter", "rs_stream")
MNSHARD_SCHEDULES = ("allgather", "overlap")
OZAKI2_KSHARD_SCHEDULES = ("psum", "reduce_scatter")


def _diag_gemms(sa, sb, pairs) -> jax.Array:
    """One anti-diagonal's exact int32 partial from local slices —
    pair order matches ``core.executors.XlaExecutor.products`` exactly
    (the bitwise-parity contract)."""
    p_t = gemm_xla(sa.slices[pairs[0][0]], sb.slices[pairs[0][1]])
    for pth, qth in pairs[1:]:
        p_t = p_t + gemm_xla(sa.slices[pth], sb.slices[qth])
    return p_t


def _local_diag_products(sa, sb, cfg: OzakiConfig):
    """[(t, int32 product)] per anti-diagonal from local slices."""
    return [(t, _diag_gemms(sa, sb, pairs)) for t, pairs in cfg.diagonals()]


def _accumulate(prods, ea, eb, cfg: OzakiConfig, w: int):
    """High-precision scaled accumulation on the reduced products —
    the identical op sequence to ``XlaExecutor.accumulate`` (ordered
    smallest terms first, one deferred ldexp), so the sharded result is
    bitwise equal to the single-device pipeline."""
    shape = prods[0][1].shape
    e_base = ea[:, None].astype(jnp.int32) + eb[None, :].astype(jnp.int32)
    if cfg.accum == "df32":
        # TPU path: compensated f32 pair, no f64 anywhere
        acc = DW(jnp.zeros(shape, jnp.float32),
                 jnp.zeros(shape, jnp.float32))
        for t, p_t in sorted(prods, key=lambda tp: -tp[0]):
            scale = jnp.float32(2.0 ** (-(t + 2) * w))
            term = int32_to_dw(p_t)
            acc = dw_add(acc, DW(term.hi * scale, term.lo * scale))
        hi = ldexp(acc.hi, e_base)
        lo = ldexp(acc.lo, e_base)
        return hi, lo                     # df32 pair (48 mantissa bits)
    c = jnp.zeros(shape, jnp.float64)
    for t, p_t in sorted(prods, key=lambda tp: -tp[0]):
        c = c + ldexp(p_t.astype(jnp.float64), e_base - (t + 2) * w)
    return c


def _kshard_local(a_blk, b_blk, cfg: OzakiConfig, axis: str, schedule: str,
                  w: int):
    """The per-device k-shard pipeline (runs inside shard_map).

    a_blk: (r, k_local) f64/f32, b_blk: (k_local, n). Returns the full
    (r, n) block (psum/overlap) or the (r, n/P) column block
    (reduce_scatter/rs_stream); df32 returns an (hi, lo) pair.
    """
    # 1. global shared exponents (pmax over the k-shards)
    ea = row_exponents(a_blk)
    eb = row_exponents(b_blk.T)
    ea = jax.lax.pmax(ea, axis)
    eb = jax.lax.pmax(eb, axis)
    # 2. local slices against the global exponents
    sa = split_int(a_blk, cfg.num_splits, w, exp=ea)
    sb = split_int(b_blk.T, cfg.num_splits, w, exp=eb)
    # 3. exact integer reduction per anti-diagonal — only int32 partials
    # (and the int32 exponent pmaxes above) ever cross a link: the f64
    # operands and the int8 slice stacks stay device-local
    if schedule == "overlap":
        # diagonal t's all-reduce is issued BEFORE diagonal t+1's GEMMs
        # are built — the independent int32 psum rides the links while
        # the next diagonal's MXU work runs (compute/comm overlap)
        prods = []
        for t, pairs in cfg.diagonals():
            prods.append((t, psum_exact_int32(
                _diag_gemms(sa, sb, pairs), axis)))
    elif schedule == "rs_stream":
        # per-diagonal reduce-scatter, issued as each diagonal's GEMMs
        # finish: no s-deep int32 stack is materialized and diagonal
        # d's collective overlaps diagonal d+1's compute
        prods = []
        for t, pairs in cfg.diagonals():
            prods.append((t, reduce_scatter_sum(
                _diag_gemms(sa, sb, pairs), axis, scatter_dim=1)))
        nloc = prods[0][1].shape[1]
        idx = jax.lax.axis_index(axis)
        eb = jax.lax.dynamic_slice_in_dim(eb, idx * nloc, nloc)
    elif schedule == "reduce_scatter":
        # int32 reduce-scatter over output columns: each chip keeps
        # its n/P column block, exactly reduced (still associative
        # -> bitwise reproducible). eb must be sliced to the block.
        prods = _local_diag_products(sa, sb, cfg)
        ts = [t for t, _ in prods]
        stacked = reduce_scatter_sum(jnp.stack([p for _, p in prods]),
                                     axis, scatter_dim=2)
        prods = list(zip(ts, stacked))
        nloc = stacked.shape[2]
        idx = jax.lax.axis_index(axis)
        eb = jax.lax.dynamic_slice_in_dim(eb, idx * nloc, nloc)
    else:
        prods = _local_diag_products(sa, sb, cfg)
        ts = [t for t, _ in prods]
        stacked = psum_exact_int32(jnp.stack([p for _, p in prods]), axis)
        prods = list(zip(ts, stacked))
    # 4. high-precision accumulation (shape follows the — possibly
    # scattered — reduced products)
    return _accumulate(prods, ea, eb, cfg, w)


def distributed_ozaki_matmul(a: jax.Array, b: jax.Array, mesh: Mesh,
                             cfg: OzakiConfig = OzakiConfig(),
                             axis: str = "model",
                             schedule: str = "psum",
                             m_axis: str | None = None) -> jax.Array:
    """FP64-accurate C = A @ B with k sharded over ``mesh[axis]``.

    a: (m, k) f64, b: (k, n) f64 (global shapes). Result is replicated
    over ``axis`` and bitwise identical for every device count.
    ``cfg.accum`` selects f64 (CPU oracle) or df32 (TPU-deployable:
    everything below stays in {int8, int32, f32}).
    ``m_axis``: additionally shard the m (row) dim — the 2D production
    layout; rows are independent in the Ozaki scheme (per-row exponents),
    so this composes with the k-shard reduction untouched.
    """
    if schedule not in KSHARD_SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{KSHARD_SCHEDULES}")
    if a.dtype == jnp.float64 or cfg.accum == "f64":
        refuse_f64_on_tpu("distributed_ozaki_matmul")
    k_global = a.shape[1]
    # fast-mode/target resolution BEFORE sizing the width, exactly like
    # the single-device drivers — required for bitwise parity on the
    # truncated-pair rows of the parity matrix
    cfg = resolve_accuracy_config(cfg, k_global)
    # Headroom: k_global terms per diagonal-fused GEMM group. The int32
    # psum adds no extra constraint beyond k_global (the global count
    # already includes every shard's terms).
    w = cfg.width_for(k_global)

    def local(a_blk, b_blk):
        return _kshard_local(a_blk, b_blk, cfg, axis, schedule, w)

    row = m_axis if m_axis else None
    col = axis if schedule in ("reduce_scatter", "rs_stream") else None
    c_spec = P(row, col)
    out_specs = (c_spec, c_spec) if cfg.accum == "df32" else c_spec
    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P(row, axis), P(axis, None)),
                               out_specs=out_specs, check_vma=False))
    out = fn(a, b)
    return DW(*out) if cfg.accum == "df32" else out


def distributed_ozaki_matmul_batched(a: jax.Array, b: jax.Array, mesh: Mesh,
                                     cfg: OzakiConfig = OzakiConfig(),
                                     axis: str = "model",
                                     batch_axis: str | None = "data",
                                     schedule: str = "psum") -> jax.Array:
    """2-D (k x batch) mesh composition: ``(B, m, k) @ (k, n)``.

    The serving layout on the host-platform recipe: the batch dim is
    sharded over ``batch_axis`` (or fully replicated with ``None``), the
    reduction over ``axis`` — broadcast weights cross the k-axis only.
    Locally the batch folds into rows (row-independent, exact — the same
    fold the unbatched serving path uses), so the k-shard schedules above
    run unchanged and the result is bitwise identical to the unsharded
    ``ozaki_matmul_batched`` for every mesh shape and schedule.
    """
    if a.ndim != 3 or b.ndim != 2:
        raise ValueError(f"expected (B, m, k) @ (k, n) broadcast weights, "
                         f"got {a.shape} @ {b.shape}")
    if schedule not in KSHARD_SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{KSHARD_SCHEDULES}")
    if a.dtype == jnp.float64 or cfg.accum == "f64":
        refuse_f64_on_tpu("distributed_ozaki_matmul_batched")
    _, m, k_global = a.shape
    cfg = resolve_accuracy_config(cfg, k_global)
    w = cfg.width_for(k_global)

    def local(a_blk, b_blk):
        bloc = a_blk.shape[0]
        folded = a_blk.reshape(bloc * m, a_blk.shape[-1])
        out = _kshard_local(folded, b_blk, cfg, axis, schedule, w)
        if cfg.accum == "df32":
            hi, lo = out
            return (hi.reshape(bloc, m, -1), lo.reshape(bloc, m, -1))
        return out.reshape(bloc, m, -1)

    row = batch_axis if batch_axis else None
    col = axis if schedule in ("reduce_scatter", "rs_stream") else None
    c_spec = P(row, None, col)
    out_specs = (c_spec, c_spec) if cfg.accum == "df32" else c_spec
    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P(row, None, axis), P(axis, None)),
                               out_specs=out_specs, check_vma=False))
    out = fn(a, b)
    return DW(*out) if cfg.accum == "df32" else out


def ozaki_matmul_mnshard(a: jax.Array, b: jax.Array, mesh: Mesh,
                         cfg: OzakiConfig = OzakiConfig(),
                         axis: str = "model",
                         schedule: str = "allgather") -> jax.Array:
    """C = A @ B with A row-sharded and B column-sharded over ``axis``.

    Full k is local, so each device splits its operand blocks against
    purely LOCAL per-row exponents (no pmax needed) and what crosses the
    mesh is the packed int8 ``SliceWire`` of B's column block — ``s``
    bytes per element + an int32 exponent vector instead of 8-byte f64
    words (``comm_bytes_model(layout="mnshard")`` prices both).

    ``schedule="allgather"``: one ring all-gather of the packed wire,
    then the plan's own executor contracts locally — bitwise-identical
    to the unsharded pipeline for EVERY backend by construction (the
    gathered representation is the exact split the reference computes,
    and rows of A are independent).

    ``schedule="overlap"``: B's slice planes are gathered one ring-hop
    chain per plane, each issued just before the first anti-diagonal
    that needs it — plane q+1's hops overlap diagonal q's GEMMs. The
    products/accumulation replicate ``XlaExecutor``'s op sequence, which
    every backend is bitwise-equal to.

    f64 operands/accumulation only (the CPU-oracle layout; the k-shard
    path owns the df32 story).
    """
    if schedule not in MNSHARD_SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{MNSHARD_SCHEDULES}")
    refuse_f64_on_tpu("ozaki_matmul_mnshard")
    if cfg.accum != "f64":
        raise ValueError("ozaki_matmul_mnshard is the f64 layout; use the "
                         "k-shard schedules for df32")
    world = mesh.shape[axis]
    m, k = a.shape
    n = b.shape[1]
    cfg = resolve_accuracy_config(cfg, k)
    w = cfg.width_for(k)
    plan = cfg.plan()
    if plan.fusion == "streaming":
        raise ValueError(
            "streaming fusion keeps slices in VMEM scratch — there is no "
            "materialized slice stack to put on the wire; use "
            "fuse_epilogue (or a non-streaming plan) with mnshard")

    def local(a_blk, b_blk):
        ex = get_executor(plan)
        sa = ex.split(a_blk, w)                    # local rows of A
        sb_loc = ex.split(b_blk.T, w)              # local cols of B (rows of B^T)
        wire = pack_slices(sb_loc)                 # (n_loc, s, k) int8 + (n_loc,)
        exp = ring_all_gather(wire.exp, axis, world)            # (n,)
        if schedule == "overlap":
            # gather plane q right before its first use; diagonals
            # ascending need planes q <= t, so plane t+1's ring hops are
            # independent of (and overlap) diagonal t's GEMMs
            planes = {}

            def plane(q):
                if q not in planes:
                    planes[q] = ring_all_gather(wire.slices[:, q, :],
                                                axis, world)    # (n, k)
                return planes[q]

            prods = []
            for t, pairs in cfg.diagonals():
                p_t = gemm_xla(sa.slices[pairs[0][0]], plane(pairs[0][1]))
                for pth, qth in pairs[1:]:
                    p_t = p_t + gemm_xla(sa.slices[pth], plane(qth))
                prods.append((t, p_t))
            return _accumulate(prods, sa.exp, exp, cfg, w)
        gathered = ring_all_gather(wire.slices, axis, world)    # (n, s, k)
        sb = SplitResult(jnp.swapaxes(gathered, 0, 1), exp, w)
        e_base = (sa.exp[:, None].astype(jnp.int32) +
                  exp[None, :].astype(jnp.int32))
        return ex.contract(sa, sb, w, e_base, (a_blk.shape[0], n))

    # check_vma=False: Pallas kernels have no shard_map replication rule
    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P(axis, None), P(None, axis)),
                               out_specs=P(axis, None), check_vma=False))
    return fn(a, b)


def _kshard_local2(a_blk, b_blk, plan, moduli, w: int, axis: str,
                   schedule: str):
    """The per-device Scheme II k-shard pipeline (runs inside shard_map).

    The residue map is per-element in k, so local residue partial
    products sum EXACTLY (int32 collectives are associative) to the
    product of the global residue operands — the reference's single
    batched GEMM. Centering, Garner digits, and the f64 CRT sum run
    once, on the reduced stack, replaying the reference's exact op
    sequence: bitwise identity for any device count. int32 headroom is
    the ``usable_moduli(k_global)`` guarantee — the global bound already
    covers every shard-partial and every psum intermediate (each is a
    partial sum of the same <= k_global bounded terms).
    """
    # 1. global shared exponents, 2. local slices against them — the
    # Scheme I k-shard discipline, unchanged
    ea = jax.lax.pmax(row_exponents(a_blk), axis)
    eb = jax.lax.pmax(row_exponents(b_blk.T), axis)
    sa = split_int(a_blk, plan.num_splits, w, exp=ea)
    sb = split_int(b_blk.T, plan.num_splits, w, exp=eb)
    # 3. local centered residues + ONE batched int8 GEMM over the
    # modulus axis: only the (ell, m, n) int32 residue partials (and the
    # int32 exponent pmaxes) ever cross a link
    ra = residues_from_slices(sa.slices, w, moduli)
    rb = residues_from_slices(sb.slices, w, moduli)
    p = gemm_xla(ra, rb)
    if schedule == "reduce_scatter":
        # scatter over output columns: each chip keeps n/P columns of
        # every modulus plane, exactly reduced; CRT runs on 1/P of the
        # output per chip. eb must follow the column block.
        p = reduce_scatter_sum(p, axis, scatter_dim=2)
        nloc = p.shape[2]
        idx = jax.lax.axis_index(axis)
        eb = jax.lax.dynamic_slice_in_dim(eb, idx * nloc, nloc)
    else:
        p = psum_exact_int32(p, axis)
    # 4. CRT reconstruction on the reduced products
    digits = crt_digits(center_mod(p, moduli), moduli)
    e_base = ea[:, None].astype(jnp.int32) + eb[None, :].astype(jnp.int32)
    return crt_value(digits, moduli, plan.beta, e_base)


def distributed_ozaki2_matmul(a: jax.Array, b: jax.Array, mesh: Mesh,
                              cfg: ModularConfig = ModularConfig(),
                              axis: str = "model",
                              schedule: str = "psum") -> jax.Array:
    """Scheme II C = A @ B with k sharded over ``mesh[axis]``.

    The residue-system sibling of ``distributed_ozaki_matmul``: global
    pmax exponents, local integerization, local centered residues, one
    local batched int8 GEMM — then ONE int32 collective over the
    stacked ``(ell, m, n)`` residue partials (``schedule="psum"``
    replicates C; ``schedule="reduce_scatter"`` leaves C column-sharded
    with half the link traffic). NO f64 operand crosses a link
    (``comm="int8"`` in the policy spec; ``comm_bytes_model`` with
    ``scheme="ozaki2_fp64"`` prices it), and the result is bitwise
    identical to the single-device reference for any mesh shape.
    """
    if schedule not in OZAKI2_KSHARD_SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{OZAKI2_KSHARD_SCHEDULES}")
    if a.dtype != jnp.float64 or b.dtype != jnp.float64:
        raise TypeError(f"distributed_ozaki2_matmul takes f64 operands, "
                        f"got {a.dtype} @ {b.dtype}")
    refuse_f64_on_tpu("distributed_ozaki2_matmul")
    k_global = a.shape[1]
    plan = cfg.plan(k_global)
    moduli = usable_moduli(k_global)[:plan.num_moduli]
    w = cfg.w

    def local(a_blk, b_blk):
        return _kshard_local2(a_blk, b_blk, plan, moduli, w, axis, schedule)

    col = axis if schedule == "reduce_scatter" else None
    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P(None, axis), P(axis, None)),
                               out_specs=P(None, col), check_vma=False))
    return fn(a, b)


def ozaki2_matmul_mnshard(a: jax.Array, b: jax.Array, mesh: Mesh,
                          cfg: ModularConfig = ModularConfig(),
                          axis: str = "model") -> jax.Array:
    """Scheme II C = A @ B with A row-sharded, B column-sharded.

    Full k is local, so operands split against purely local exponents;
    what crosses the mesh is the packed ``ResidueWire`` of B's column
    block — ``ell`` bytes per element + an int32 exponent vector instead
    of 8-byte f64 words (vs the SliceWire's ``s``: the residue wire wins
    exactly when ``ell < s``, the same arbitration
    ``comm_bytes_model(scheme="ozaki2_fp64", layout="mnshard")``
    encodes). The gathered stack IS the residue operand the reference
    executor computes, so every backend — including the fused-CRT
    epilogue kernel — contracts it to the bitwise-identical result.
    """
    refuse_f64_on_tpu("ozaki2_matmul_mnshard")
    world = mesh.shape[axis]
    k = a.shape[1]
    plan = cfg.plan(k)
    moduli = usable_moduli(k)[:plan.num_moduli]
    w = cfg.w

    def local(a_blk, b_blk):
        ex = get_executor(plan)
        sa = ex.split(a_blk, w)                     # local rows of A
        sb_loc = ex.split(b_blk.T, w)               # local cols of B
        rb_loc = residues_from_slices(sb_loc.slices, w, moduli)
        wire = pack_residues(rb_loc, sb_loc.exp, moduli)  # (n_loc, ell, k)
        gathered = ResidueWire(
            ring_all_gather(wire.residues, axis, world),   # (n, ell, k)
            ring_all_gather(wire.exp, axis, world),        # (n,)
            wire.moduli)
        rb, exp = unpack_residues(gathered)                # (ell, n, k)
        ra = residues_from_slices(sa.slices, w, moduli)
        e_base = (sa.exp[:, None].astype(jnp.int32) +
                  exp[None, :].astype(jnp.int32))
        if plan.fusion == "epilogue":
            from repro.kernels import int8_matmul_nt_crt
            mods, qmod, inv, scales = garner_constants(moduli, plan.beta)
            tile = plan.tile
            out = int8_matmul_nt_crt(ra, rb, moduli=mods, qmod=qmod,
                                     inv=inv, scales=scales, bm=tile.bm,
                                     bn=tile.bn, bk=tile.bk,
                                     interpret=plan.interpret)
            return ldexp(out, e_base)
        p = ex.gemm(ra, rb)
        digits = crt_digits(center_mod(p, moduli), moduli)
        return crt_value(digits, moduli, plan.beta, e_base)

    # check_vma=False: Pallas kernels have no shard_map replication rule
    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P(axis, None), P(None, axis)),
                               out_specs=P(axis, None), check_vma=False))
    return fn(a, b)


def kshard_specs(a_ndim: int, b_ndim: int, axis: str) -> tuple[P, P]:
    """PartitionSpecs placing the contraction (k) dim on ``axis``.

    Handles every operand rank of the (batched) Ozaki API: a is
    (m, k) or (B, m, k); b is (k, n) or (B, k, n).
    """
    a_spec = P(None, None, axis) if a_ndim == 3 else P(None, axis)
    b_spec = P(None, axis, None) if b_ndim == 3 else P(axis, None)
    return a_spec, b_spec


def ozaki_matmul_kshard_auto(a: jax.Array, b: jax.Array, mesh: Mesh,
                             cfg: OzakiConfig = OzakiConfig(),
                             axis: Optional[str] = None) -> jax.Array:
    """Paper-faithful distributed baseline: the (batched) Ozaki pipeline
    under jit with k-sharded inputs — GSPMD inserts the collectives
    (f64 operand words move around the opaque kernels; reproducible only
    per mesh shape).

    ``cfg.comm="int8"`` re-routes onto the explicit int8-slice collective
    schedules (``distributed_ozaki_matmul``/``_batched``): NO f64 operand
    crosses a link, only exact int32 pair partials + exponent pmaxes —
    and the result upgrades from per-mesh-shape reproducible to bitwise
    identical to the single-device reference for ANY mesh shape.
    Covered routes: f64 2-D, and f64 3-D with broadcast (2-D) weights —
    stacked 3-D weights and df32 stay on the GSPMD path.

    3-D ``a`` routes through ``ozaki_matmul_batched`` (stacked or
    broadcast ``b``), composing the batched API with k-sharding: the
    executor pipeline is unchanged, only the operand layout differs. The
    resolved axis is recorded on the config (``shard_axis``), so the
    ``PipelinePlan`` built inside the jitted computation carries it.
    """
    from repro.core.ozaki import ozaki_matmul, ozaki_matmul_batched
    axis = axis or cfg.shard_axis or "model"
    cfg = dataclasses.replace(cfg, shard_axis=axis)
    if getattr(cfg, "comm", "f64") == "int8" and cfg.accum == "f64" and \
            a.dtype == jnp.float64:
        if a.ndim == 2:
            return distributed_ozaki_matmul(a, b, mesh, cfg, axis=axis)
        if a.ndim == 3 and b.ndim == 2:
            return distributed_ozaki_matmul_batched(
                a, b, mesh, cfg, axis=axis, batch_axis=None)
    impl = ozaki_matmul_batched if a.ndim == 3 else ozaki_matmul
    a_spec, b_spec = kshard_specs(a.ndim, b.ndim, axis)
    out_spec = P(*([None] * a.ndim))
    fn = jax.jit(functools.partial(impl, cfg=cfg),
                 in_shardings=(NamedSharding(mesh, a_spec),
                               NamedSharding(mesh, b_spec)),
                 out_shardings=NamedSharding(mesh, out_spec))
    return fn(a, b)


# ----------------------------------------------------------------------------
# Deployment wiring: an ambient shard mesh + in-trace sharding hints, so the
# model/serving layers can honor ``ozaki_shard_axis`` without threading a
# Mesh through every projection call.
# ----------------------------------------------------------------------------

_SHARD_MESH: list = [None]


def set_shard_mesh(mesh: Optional[Mesh]) -> None:
    """Register (or clear, with None) the deployment's shard mesh.

    Trace-time semantics: the registry is read while a jitted function
    TRACES, not when it runs — register the mesh before the first call
    of any jitted step that should honor it (a cached executable traced
    without a mesh stays unsharded until a shape change retraces it).
    The serving engine scopes its mesh around every tick
    (``use_shard_mesh``), which covers the first trace by construction.
    """
    _SHARD_MESH[0] = mesh


def active_shard_mesh() -> Optional[Mesh]:
    return _SHARD_MESH[0]


@contextlib.contextmanager
def use_shard_mesh(mesh: Optional[Mesh]):
    prev = _SHARD_MESH[0]
    _SHARD_MESH[0] = mesh
    try:
        yield mesh
    finally:
        _SHARD_MESH[0] = prev


def _constrain(x: jax.Array, sharding: NamedSharding) -> jax.Array:
    if isinstance(x, jax.core.Tracer):          # inside jit: GSPMD hint
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)          # eager: reshard now


def constrain_batched_kshard(a: jax.Array, b: jax.Array, axis: str,
                             mesh: Optional[Mesh] = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Pin the k dim of a (batched) matmul's operands to ``mesh[axis]``.

    The in-trace composition point for ``OzakiConfig.shard_axis`` /
    ``ArchConfig.ozaki_shard_axis``: unlike ``ozaki_matmul_kshard_auto``
    (which owns its jit), this works inside an already-traced model step.
    No-op when no mesh is registered or the axis is absent from it.
    """
    mesh = mesh if mesh is not None else active_shard_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return a, b
    a_spec, b_spec = kshard_specs(a.ndim, b.ndim, axis)
    return (_constrain(a, NamedSharding(mesh, a_spec)),
            _constrain(b, NamedSharding(mesh, b_spec)))
