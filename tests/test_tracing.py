"""Stage scopes, host spans and counters of ``repro.tracing``.

The scopes must reach every operation of the DW front door's stage work
through the lowered program's ``op_name`` metadata, and change nothing
else: the program lowered without them is the same text once the
metadata is stripped. The counters count what the program issued.
"""
import contextlib
import math
import re

import jax
import jax.numpy as jnp
import pytest

import repro
from repro import tracing
from repro.core.xmath import DW
from repro.kernels import launch

SPEC = "ozaki-fp64x9/pallas_fused+epilogue"
ROUTES = {"2d": ((48, 256), (256, 40)), "batch_fold": ((2, 24, 256), (256, 40))}
# instructions of the entry computation that carry no work of a stage:
# arguments, constants (zero-fills lower to broadcasts of a constant,
# which keep no metadata) and the output tuple
_NO_STAGE = ("parameter", "constant", "tuple")
_INSTR = re.compile(r"^\s+(?:ROOT )?(\S+) = .*? ([a-z][a-z0-9\-]*)\(")


def _front_door():
    return jax.jit(lambda ah, al, bh, bl: repro.matmul(
        DW(ah, al), DW(bh, bl), precision=SPEC))


def _lower(route):
    a, b = ROUTES[route]
    f32 = lambda d: jax.ShapeDtypeStruct(d, jnp.float32)  # noqa: E731
    return _front_door().lower(f32(a), f32(a), f32(b), f32(b))


def _entry_ops(hlo: str):
    """``(name, opcode, op_name)`` of the entry computation's instructions."""
    entry = hlo[hlo.index("\nENTRY"):]
    out = []
    for line in entry.splitlines()[1:]:
        m = _INSTR.match(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), op.group(1) if op else None))
    return out


def _stage(op_name):
    stages = [p for p in (op_name or "").split("/") if p.startswith("ozaki.")]
    return stages[-1] if stages else None


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_every_stage_names_its_operations(route):
    hlo = _lower(route).as_text(dialect="hlo", debug_info=True)
    ops = _entry_ops(hlo)
    assert {_stage(o) for _, _, o in ops} >= set(tracing.STAGES)
    unscoped = [(n, c, o) for n, c, o in ops if _stage(o) is None
                and c not in _NO_STAGE
                and not (c == "broadcast" and o is None)]
    assert not unscoped
    # every stage sits inside the front door's own scope
    assert all(o.startswith(f"jit(<lambda>)/{tracing.MATMUL}/")
               for _, _, o in ops if _stage(o))


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_scopes_change_only_the_metadata(route, monkeypatch):
    scoped = _lower(route).as_text()
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    plain = _lower(route).as_text()
    assert scoped == plain
    assert tracing.SCALE_OUT not in _lower(route).as_text(
        dialect="hlo", debug_info=True)


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_counters_count_one_trace(route):
    a, b = ROUTES[route]
    before = tracing.counters()
    jax.eval_shape(lambda ah, al, bh, bl: repro.matmul(
        DW(ah, al), DW(bh, bl), precision=SPEC),
        *[jax.ShapeDtypeStruct(d, jnp.float32) for d in (a, a, b, b)])
    after = tracing.counters()
    delta = {k: after[k] - before[k] for k in tracing.COUNTERS}
    # s = 9: 9 anti-diagonal groups of 1..9 pairs, 45 pairs in all
    assert delta == {"matmul_traces": 1, "plans": 1, "gemm_launches": 9,
                     "gemm_pairs": 45}


def test_counters_follow_the_launch_schedule():
    """The xla backend launches one GEMM per pair; ``:fast`` drops the
    last anti-diagonal (9 pairs) from the launches."""
    a = jax.ShapeDtypeStruct((16, 64), jnp.float64)
    b = jax.ShapeDtypeStruct((64, 8), jnp.float64)
    for spec, launches, pairs in (("ozaki-fp64x9/xla", 45, 45),
                                  ("ozaki-fp64x9:fast/pallas_fused+epilogue",
                                   8, 36)):
        before = tracing.counters()
        jax.eval_shape(lambda x, y: repro.matmul(x, y, precision=spec), a, b)
        after = tracing.counters()
        assert after["gemm_launches"] - before["gemm_launches"] == launches
        assert after["gemm_pairs"] - before["gemm_pairs"] == pairs


# operand shapes of the chip benchmark's cells: dgemm.square16k,
# batched.serve4k (the batch folds into rows), dgemm.rank256
CELLS = {"square16k": ((16384, 16384), (16384, 16384)),
         "serve4k": ((32, 128, 4096), (4096, 4096)),
         "rank256": ((16384, 256), (256, 16384))}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_grid_steps_at_cell_shapes(cell):
    """One front-door trace at a cell's shape issues the grid steps of
    ``epilogue_blocks``: 45 pairs over (m/bm)(n/bn)(k/bk) tiles, at least
    4x fewer than the 256 x 256 x 512 blocks the launches took before
    (184,320 steps at rank256). Traced with ``jax.eval_shape``: nothing
    runs."""
    a, b = CELLS[cell]
    m, k, n = math.prod(a[:-1]), a[-1], b[-1]
    before = tracing.grid_steps()
    jax.eval_shape(lambda ah, al, bh, bl: repro.matmul(
        DW(ah, al), DW(bh, bl), precision=SPEC),
        *[jax.ShapeDtypeStruct(d, jnp.float32) for d in (a, a, b, b)])
    steps = tracing.grid_steps() - before
    bm, bn, bk = launch.epilogue_blocks(m, n, k, planes=2, el_bytes=4)
    assert steps == 45 * (m // bm) * (n // bn) * (k // bk)
    old_steps = 45 * (m // 256) * (n // 256) * -(-k // 512)
    assert old_steps >= 4 * steps
    if cell == "rank256":
        assert old_steps == 184_320


def test_eager_calls_count_each_call(rng):
    a = jnp.asarray(rng.standard_normal((8, 32)))
    b = jnp.asarray(rng.standard_normal((32, 8)))
    before = tracing.counters()
    for _ in range(2):
        repro.matmul(a, b, precision="ozaki-fp64x3/xla")
    after = tracing.counters()
    assert after["matmul_traces"] - before["matmul_traces"] == 2
    assert after["plans"] - before["plans"] == 2


def test_unknown_names_are_refused():
    with pytest.raises(ValueError):
        tracing.scope("ozaki.other")
    with pytest.raises(ValueError):
        tracing.span("call")
    with pytest.raises(ValueError):
        tracing.count("launches")
    # grid steps are counted with the launches, and read apart
    with pytest.raises(ValueError):
        tracing.count(tracing.GRID_STEPS)
