"""Stages from the program's scopes, idle gaps put down to the runtime,
and the readers of ``exponents_ms``, ``layout_ms`` and ``scale_out_ms``:
on the recorded traces, on hand-made events, and on programs compiled
here for the CPU."""
import dataclasses
import gc
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import idle, scopes
from benchmarks.chip.counts import Plan, call_counts
from benchmarks.chip.harness import CHIP_DIR, Bench
from benchmarks.chip.peaks import peaks_for
from benchmarks.chip.trace import window_of

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")
STAGE_READERS = {"exponents_ms": "ozaki.exponents",
                 "layout_ms": "ozaki.layout",
                 "scale_out_ms": "ozaki.scale_out"}


@pytest.fixture(scope="module")
def bench():
    return Bench()


def _old_fixture():
    d = json.load(open(os.path.join(DATA, "serve4k_two_calls.json")))
    ops = {int(c): [tuple(x) for x in evs] for c, evs in d["ops"].items()}
    return window_of(ops, [tuple(x) for x in d["spans"]])


# ---------------------------------------------------------------------------
# what the readers already in BENCHMARK.json read stays as it was
# ---------------------------------------------------------------------------

def test_stage_readers_claim_no_operations(bench):
    # glue_ms leaves out what a reader claims by ``match``: the new
    # readers are parts of glue and claim nothing
    for name in STAGE_READERS:
        assert not hasattr(bench.reader(name), "match")
    assert sorted(bench.stages()) == ["gemm_roofline", "split_ms"]


@pytest.mark.parametrize("metric,value", [
    ("glue_ms", 0.8526595), ("split_ms", 5.2066375),
    ("gemm_roofline", 27.327372146157046),
    ("device_idle_pct", 2.5773274894893117)])
def test_existing_metrics_read_as_before_on_the_old_trace(bench, metric,
                                                          value):
    r = _old_fixture()
    calls = [call_counts(4096, 4096, 4096, Plan(9, 45, 9))] * r.calls
    run = SimpleNamespace(trace=r, calls=calls, stages=bench.stages(),
                          peaks=peaks_for("TPU v5 lite"))
    assert bench.reader(metric).read(run) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_readers_read_nothing_without_scopes(bench, name,
                                                   monkeypatch):
    """The parent program carries no scopes: the readers stay silent."""
    unscoped = scopes.parse_program(_hlo(scoped=False))
    monkeypatch.setattr(scopes, "live_programs", lambda: [unscoped])
    run = SimpleNamespace(trace=_old_fixture(), calls=[None, None])
    assert bench.reader(name).read(run) is None
    assert bench.reader(name).read(SimpleNamespace(trace=None)) is None


# ---------------------------------------------------------------------------
# stages from compiled programs
# ---------------------------------------------------------------------------

def _hlo(scoped: bool = True) -> str:
    """A small program's compiled text, with stage scopes or without."""
    def f(bh, x):
        b_t = bh.T
        if not scoped:
            return jnp.sin(b_t) @ x
        with jax.named_scope("ozaki.exponents"):
            e = jnp.sin(b_t)
        with jax.named_scope("ozaki.gemm"):
            return e @ x
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    return jax.jit(f).lower(x, x).compile().as_text()


def test_parse_program_keeps_order_metadata_and_arguments():
    p = scopes.parse_program(_hlo())
    assert p.name.startswith("jit_f") and p.scoped
    assert p.args == {"bh", "x"}
    stages = [p.stage(i) for i in sorted(p.order, key=p.order.get)]
    assert "ozaki.exponents" in stages and "ozaki.gemm" in stages
    assert list(p.order.values()) == list(range(len(p.order)))
    assert not scopes.parse_program(_hlo(scoped=False)).scoped


@pytest.mark.parametrize("op_name,stage", [
    ("jit(<lambda>)/repro.matmul/ozaki.split/ozaki.exponents/reduce_max",
     "ozaki.exponents"),
    ("jit(<lambda>)/repro.matmul/ozaki.gemm/ozaki.scale_out/jit(ldexp)/"
     "jit(_where)/select_n:", "ozaki.scale_out"),
    ("jit(<lambda>)/repro.matmul/ozaki.split/jit(fused_split_dw)/"
     "fused_split_dw/pallas_call", "ozaki.split"),
    ("bh", "ozaki.layout"), ("bh:", "ozaki.layout"),
    ("jit(take)/gather", None), ("hi", None), (None, None)])
def test_stage_is_the_innermost_scope(op_name, stage):
    assert scopes.stage_of(op_name, args={"ah", "al", "bh", "bl"}) == stage


def _program(name, instrs, scoped=True):
    return scopes.Program(
        name=name, order={n: i for i, n in enumerate(instrs)},
        op_names={n: (f"jit(f)/ozaki.gemm/{n}" if scoped else f"jit(g)/{n}")
                  for n in instrs},
        args=frozenset())


def test_attribution_follows_each_programs_schedule():
    timed = _program("timed", ["fusion.1", "copy", "kernel.9", "fusion"])
    take = _program("take", ["copy-start", "fusion", "copy", "copy-done"],
                    scoped=False)
    names = (["fusion.1", "copy", "kernel.9", "fusion"]
             + ["copy-start", "fusion", "copy", "copy-done"]
             + ["fusion.1", "copy", "kernel.9", "fusion"])
    got = [p.name for p in scopes.attribute(names, [timed, take])]
    assert got == ["timed"] * 4 + ["take"] * 4 + ["timed"] * 4
    assert scopes.attribute(["other"], [timed, take]) == [None]


# ---------------------------------------------------------------------------
# idle gaps put down to the runtime
# ---------------------------------------------------------------------------

MS = 1_000_000


def _two_programs(completion_lag_ms: float) -> idle.Detail:
    """One call: the product (two ops, 1-10 ms and 11-20 ms: a 1 ms gap
    inside it), then the sample ``take``, launched once the product has
    completed (21 ms) and run at 21.5-22 ms, inside a window 0-23 ms."""
    lag = int(completion_lag_ms * MS)
    ops = {0: [("a.1", "fusion", 1 * MS, 10 * MS, "jit(f)/ozaki.split/x"),
               ("k.9", "custom-call", 11 * MS, 20 * MS,
                "jit(f)/ozaki.gemm/x"),
               ("fusion", "fusion", 21 * MS + lag + MS // 2,
                22 * MS + lag, "jit(take)/gather")]}
    modules = {0: [("jit_f", 1 * MS, 20 * MS),
                   ("jit_take", 21 * MS + lag + MS // 2, 22 * MS + lag)]}
    spans = [("call", 0, MS // 2), ("check", MS // 2, MS),
             ("wait", MS, 23 * MS + lag)]
    launches = [(MS // 4, MS // 2), (21 * MS + lag + MS // 10,
                                       21 * MS + lag + MS // 5)]
    completions = [(20 * MS + lag, 21 * MS + lag),
                   (22 * MS + lag + MS // 4, 22 * MS + lag + MS // 2)]
    return idle.Detail(ops, modules, spans, launches, completions)


def test_idle_pieces_tile_the_idle_time():
    d = _two_programs(0.0)
    r = d.reduced()
    causes = idle.idle_by_cause(d, 0)
    assert sum(causes.values()) == pytest.approx(r.window_s - r.busy_s)
    # dispatch: first call to first op, then from each completion read
    # to the next op (or the window's end); in_call: the gap inside the
    # product; completion: product read at 21 ms, take at 22.5 ms
    assert causes == pytest.approx({
        "dispatch": 1.0e-3 + 0.5e-3 + 0.5e-3, "in_call": 1.0e-3,
        "completion": 1.0e-3 + 0.5e-3, "trace": 0.0, "compile": 0.0})
    assert idle.clock_margins(d, 0) == pytest.approx(
        {"launch_s": 0.4e-3, "completion_s": 0.0, "shift_s": 0.0,
         "programs": 2})


def test_a_gap_before_a_program_already_launched_is_dispatch():
    """The take launched during the product: the device's pause between
    the two is launch latency, not the product's completion."""
    d = _two_programs(0.0)
    d.launches[1] = (MS // 2 + 1, MS)
    causes = idle.idle_by_cause(d, 0)
    assert causes["completion"] == pytest.approx(0.5e-3)   # take's, at end
    assert causes["dispatch"] == pytest.approx(1.0e-3 + 1.5e-3 + 0.5e-3)


def test_host_times_move_onto_the_device_clock():
    """A trace whose device clock reads 1 ms early shows the product's
    first operation 0.25 ms before its launch: host times move back by
    that much, and the idle split is the one of the aligned trace."""
    d = _two_programs(0.0)
    aligned = idle.idle_by_cause(d, 0)
    d.launches[0] = (MS + MS // 4, MS + MS // 2)
    d.completions = [(s + MS // 4, e + MS // 4) for s, e in d.completions]
    d.runtime = []
    margins = idle.clock_margins(d, 0)
    assert margins["launch_s"] == pytest.approx(-0.25e-3)
    assert margins["shift_s"] == pytest.approx(0.25e-3)
    assert idle.idle_by_cause(d, 0) == pytest.approx(aligned)


def test_each_program_has_one_launch_and_one_done():
    """One sync-flag read can complete two programs: the completions are
    the ``Execute=>Done`` events, one a program."""
    runtime = [("main", "PJRT_LoadedExecutable_Execute", 0, 5),
               ("main", "PJRT_LoadedExecutable_Execute", 6, 9),
               ("poll", "ReadSyncFlag", 20, 22),
               ("poll", "tpu::System::Execute=>Done", 25, 26),
               ("poll", "tpu::System::Execute=>Done", 26, 27)]
    launches, completions = idle.runtime_events(runtime)
    assert launches == [(0, 5), (6, 9)]
    assert completions == [(25, 26), (26, 27)]


def test_a_late_completion_is_named_completion():
    """The device sat idle for 90 ms while the runtime had not yet read
    the product's completion: the gap is the runtime's, not the
    caller's."""
    d = _two_programs(90.0)
    gaps = idle.named_gaps(d, 0)
    assert gaps[0][0] == "completion"
    assert gaps[0][1] == pytest.approx(91.5e-3)
    causes = idle.idle_by_cause(d, 0)
    assert causes["completion"] == pytest.approx(91.5e-3)
    assert {c for c, _ in gaps} <= set(idle.CAUSES)


def test_program_spans_and_compiles_inside_a_gap():
    d = _two_programs(0.0)
    d.spans += [("repro.matmul", MS // 8, MS // 4),
                ("backend_compile_and_load", MS // 4, MS // 2)]
    causes = idle.idle_by_cause(d, 0)
    assert causes["trace"] == pytest.approx(0.125e-3)
    assert causes["compile"] == pytest.approx(0.25e-3)
    assert causes["dispatch"] == pytest.approx(2.0e-3 - 0.375e-3)
    r = d.reduced()
    assert sum(causes.values()) == pytest.approx(r.window_s - r.busy_s)


# ---------------------------------------------------------------------------
# the program's counters beside counts.Plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["dgemm.square16k", "batched.serve4k",
                                  "dgemm.rank256"])
def test_program_counts_the_pairs_the_plan_counts(bench, cell):
    from repro import tracing
    c = bench.cell(cell)
    route = bench.route(c, c.config["entry"], jax.devices()[:1])
    ops = c.traffic["operands"]
    lhs, rhs = c.traffic["calls"][0]
    a, b = ops[lhs], ops[rhs]
    word = jax.ShapeDtypeStruct
    before = tracing.counters()
    jax.eval_shape(route.call, (word(a, jnp.float32), word(a, jnp.float32)),
                   (word(b, jnp.float32), word(b, jnp.float32)))
    after = tracing.counters()
    m, n, k = int(jnp.prod(jnp.array(a[:-1]))), b[-1], a[-1]
    plan = route.plan(m, n, k)
    assert after["matmul_traces"] - before["matmul_traces"] == 1
    assert after["gemm_pairs"] - before["gemm_pairs"] == plan.pairs == 45
    assert after["gemm_launches"] - before["gemm_launches"] == \
        plan.groups == 9


def _tiny():
    return Bench(os.path.join(FIXTURE, "BENCHMARK.json"),
                 dirs=(FIXTURE, CHIP_DIR))


def test_traced_window_counts_no_trace_inside_the_window(tmp_path):
    from benchmarks.chip.detail import traced_window
    w = traced_window(_tiny(), "tiny.serve", 3_000_000_011, 0.0,
                      str(tmp_path / "trace"), require_tpu=False)
    # one shape pair: one trace and one plan in the warm-up, nothing in
    # the window
    assert w["warm_counts"] == {"matmul_traces": 1, "plans": 1,
                                "gemm_pairs": 45, "gemm_launches": 9}
    assert set(w["window_counts"].values()) == {0}
    assert w["products"] == [(32, 64, 96)]
    assert any(f.endswith(".xplane.pb") for _, _, fs in os.walk(tmp_path)
               for f in fs)


def _recording_routes(bench, monkeypatch) -> list:
    """Make ``bench``'s routes log each timed call (its operands' sums
    and whether the cyclic collector is on) and each sample's indices."""
    log = []
    build = bench.route

    def sums(x):
        return tuple(float(np.asarray(v, np.float64).sum())
                     for v in jax.tree.leaves(x))

    def route(cell, entry, devices):
        r = build(cell, entry, devices)

        def call(lhs, rhs):
            log.append(("call", sums(lhs), sums(rhs), gc.isenabled()))
            return r.call(lhs, rhs)

        def sample(out, idx):
            log.append(("sample", [np.asarray(v).tolist() for pair in idx
                                   for v in pair]))
            return r.sample(out, idx)

        return dataclasses.replace(r, call=call, sample=sample)

    monkeypatch.setattr(bench, "route", route)
    return log


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.swap"])
def test_traced_window_runs_the_harness_loop(tmp_path, monkeypatch, cell):
    """``detail.py`` reads a window of the harness's own loop: the same
    warm-up, operands, rotating sample sets and collector state."""
    from benchmarks.chip.detail import traced_window
    from benchmarks.chip.harness import run_cell
    seed = 3_000_000_011
    tiny = _tiny()
    log = _recording_routes(tiny, monkeypatch)
    run_cell(tiny, cell, seed, 0.0, require_tpu=False)
    harness = list(log)
    log.clear()
    traced_window(tiny, cell, seed, 0.0, str(tmp_path / "trace"),
                  require_tpu=False)
    assert log == harness
    calls = [e for e in harness if e[0] == "call"]
    samples = [e[1] for e in harness if e[0] == "sample"]
    warm = len(calls) - 3
    # the collector on in the warm-up and off in the window; the window's
    # third call (the cycle's first product again) samples another set
    assert [e[3] for e in calls] == [True] * warm + [False] * 3
    assert samples[warm] != samples[warm + 2]


# ---------------------------------------------------------------------------
# a traced window recorded on the chip with the scoped program
# ---------------------------------------------------------------------------

DETAIL = os.path.join(DATA, "serve4k_detail_two_calls.json")


@pytest.fixture(scope="module")
def recorded():
    """``(Detail, programs)`` of two serve4k calls recorded by
    ``detail.py`` on a TPU v5 lite."""
    d = json.load(open(DETAIL))
    launches, completions = idle.runtime_events(d["runtime"])
    detail = idle.Detail(
        ops={int(c): [tuple(x) for x in evs] for c, evs in d["ops"].items()},
        modules={int(c): [tuple(x) for x in evs]
                 for c, evs in d["modules"].items()},
        spans=[tuple(x) for x in d["spans"]], launches=launches,
        completions=completions, runtime=[tuple(x) for x in d["runtime"]])
    programs = [scopes.Program(
        name=p["name"], order={n: i for i, n in enumerate(p["order"])},
        op_names=dict(zip(p["order"], p["op_names"])),
        args=frozenset(p["args"])) for p in d["programs"]]
    return detail, programs


def _window_ops(detail):
    r = detail.reduced()
    return r, [e for e in detail.ops[0] if e[3] > r.start and e[2] < r.end]


def test_recorded_stages_from_programs_agree_with_the_trace(recorded):
    detail, programs = recorded
    r, ops = _window_ops(detail)
    args = frozenset().union(*(p.args for p in programs if p.scoped))
    by_trace = [scopes.stage_of(e[4], args) for e in ops]
    ran = scopes.attribute([e[0] for e in ops], programs)
    assert all(p is not None for p in ran)
    assert [p.stage(e[0]) for e, p in zip(ops, ran)] == by_trace
    assert set(by_trace) >= {"ozaki.layout", "ozaki.exponents",
                             "ozaki.split", "ozaki.gemm", "ozaki.scale_out"}


def test_recorded_stage_metrics_add_up_to_glue(recorded, bench,
                                               monkeypatch):
    detail, programs = recorded
    monkeypatch.setattr(scopes, "live_programs", lambda: programs)
    r, ops = _window_ops(detail)
    args = frozenset().union(*(p.args for p in programs if p.scoped))
    run = SimpleNamespace(trace=r, calls=[None] * r.calls,
                          stages=bench.stages())

    def clipped(e):
        return (min(e[3], r.end) - max(e[2], r.start)) * 1e-9

    matches = list(bench.stages().values())
    glue = {}
    for e in ops:
        if not any(f(e[0], e[1]) for f in matches):
            st = scopes.stage_of(e[4], args)
            glue[st] = glue.get(st, 0.0) + clipped(e) / r.calls * 1e3
    for name, stage in STAGE_READERS.items():
        assert bench.reader(name).read(run) == pytest.approx(glue[stage])
        assert glue[stage] > 0
    # the stage readers, the split and gemm scopes' glue and the unscoped
    # rest make up glue_ms
    assert sum(glue.values()) == pytest.approx(
        bench.reader("glue_ms").read(run))


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_recorded_stage_readers_read_nothing_with_a_program_gone(
        recorded, bench, name, monkeypatch):
    """An operation put down to no program the process holds has no
    known stage: the readers stay silent rather than read low."""
    detail, programs = recorded
    held = [p for p in programs if p.scoped]
    assert len(held) < len(programs)    # the sample ``take`` is gone
    monkeypatch.setattr(scopes, "live_programs", lambda: held)
    r, _ = _window_ops(detail)
    run = SimpleNamespace(trace=r, calls=[None] * r.calls)
    assert bench.reader(name).read(run) is None


def test_recorded_idle_splits_by_cause(recorded):
    detail, _ = recorded
    r = detail.reduced()
    causes = idle.idle_by_cause(detail, 0)
    assert sum(causes.values()) == pytest.approx(r.window_s - r.busy_s,
                                                 rel=1e-9)
    assert causes["completion"] > 0 and causes["dispatch"] > 0
    assert causes["trace"] == causes["compile"] == 0
    gaps = idle.named_gaps(detail, 0)
    assert {c for c, _ in gaps} <= set(idle.CAUSES)
    # the two calls' four programs and the next call's product, which
    # the device's clock, 1.2 ms early in this trace, puts in the window
    m = idle.clock_margins(detail, 0)
    assert m["programs"] == 5
    assert m["launch_s"] < 0 and m["shift_s"] == -m["launch_s"]
    assert m["completion_s"] > m["shift_s"]
    # one gap a call, after the sample: the product and the sample
    # complete on the host together, about 0.9 ms after the device
    assert [c for c, s in gaps if s > 1e-4] == ["completion"] * 2
