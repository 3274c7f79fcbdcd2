"""The reduction from a device trace to per-layer metrics, on a small
trace recorded on a TPU v5 lite and on hand-made intervals."""
import json
import os

import pytest

from benchmarks.chip.harness import ROOT, Bench
from benchmarks.chip.trace import Reduced, parse_op, window_of

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "serve4k_two_calls.json")


@pytest.fixture(scope="module")
def recorded():
    d = json.load(open(DATA))
    ops = {int(c): [tuple(x) for x in evs] for c, evs in d["ops"].items()}
    return window_of(ops, [tuple(x) for x in d["spans"]])


@pytest.fixture(scope="module")
def readers():
    b = Bench()
    return {m: b.reader(m) for m in ("split_ms", "gemm_roofline",
                                     "collective_ms", "glue_ms")}


def test_parse_op_keeps_name_and_opcode():
    assert parse_op("%int8_matmul_nt_epilogue_dw.9 = (f32[8,8]{1,0:T(8,128)}"
                    ", f32[8,8]{1,0}) custom-call(s8[9,8,8]{2,1,0} %a)") == \
        ("int8_matmul_nt_epilogue_dw.9", "custom-call")
    assert parse_op("%psum.6 = s32[9,8192,8192]{2,1,0:T(8,128)} all-reduce("
                    "%x), channel_id=1") == ("psum.6", "all-reduce")
    assert parse_op("%fusion.14 = f32[16384]{0:T(1024)S(1)} fusion(%p), "
                    "kind=kLoop, calls=%fused_computation.12") == \
        ("fusion.14", "fusion(kLoop)")
    # XLA:TPU fuses an int8 dot with its add into an output fusion
    assert parse_op("%convolution_add_fusion.4 = s32[8192,8192]{1,0:T(8,128)}"
                    " fusion(%a, %b), kind=kOutput, calls=%fc.4") == \
        ("convolution_add_fusion.4", "fusion(kOutput)")
    assert parse_op("jit_take") == ("jit_take", "")


def test_recorded_window_is_two_whole_calls(recorded):
    assert recorded.calls == 2
    calls = [s for s in recorded.spans if s[0] == "call"]
    waits = [s for s in recorded.spans if s[0] == "wait"]
    assert recorded.start == calls[0][1] and recorded.end == waits[-1][2]
    assert all(recorded.start <= s < e <= recorded.end
               for _, _, s, e in recorded.ops[0])


def test_kshard_dots_count_as_gemm(readers):
    match = readers["gemm_roofline"].match
    assert match("convolution.47", "convolution")
    assert match("bitcast_dynamic-update-slice_fusion.8", "fusion(kOutput)")
    assert not match("floor_subtract_fusion.4", "fusion(kLoop)")
    assert readers["collective_ms"].match("psum.6", "all-reduce")
    assert not readers["collective_ms"].match("fusion.1", "fusion(kLoop)")


def test_recorded_stages_are_classified(recorded, readers):
    ops = recorded.ops[0]
    split = [x for x in ops if readers["split_ms"].match(x[0], x[1])]
    gemm = [x for x in ops if readers["gemm_roofline"].match(x[0], x[1])]
    coll = [x for x in ops if readers["collective_ms"].match(x[0], x[1])]
    # two operands split per call; one epilogue kernel per anti-diagonal
    # group (9 at s = 9) per call; no collective on one chip
    assert len(split) == 4 and len(gemm) == 18 and not coll
    assert all(o == "custom-call" for _, o, _, _ in split + gemm)
    split_s = sum(e - s for *_, s, e in split) * 1e-9
    assert recorded.seconds(readers["split_ms"].match) == \
        pytest.approx(split_s)
    total = sum(e - s for *_, s, e in ops) * 1e-9
    glue = total - split_s - sum(e - s for *_, s, e in gemm) * 1e-9
    assert glue > 0
    # the kernels take most of a call, the split a few ms of it
    assert 0.05 < recorded.seconds(readers["gemm_roofline"].match) < 0.13
    assert 0.005 < split_s < 0.02


def test_recorded_busy_and_idle(recorded):
    ops = recorded.ops[0]
    assert max(e - s for *_, s, e in ops) * 1e-9 < recorded.busy_s
    assert recorded.busy_s <= recorded.window_s
    idle = recorded.window_s - recorded.busy_s
    assert 0 < idle < 0.1 * recorded.window_s
    gaps = recorded.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(idle, rel=1e-9)
    assert {n for n, _ in gaps} <= {"call", "wait", "check", "host"}
    bd = recorded.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("int8_matmul_nt_epilogue_dw")


def _hand_made():
    ops = {0: [("a.1", "fusion", 10, 20), ("b.1", "custom-call", 15, 30),
               ("c.1", "fusion", 40, 50), ("d.1", "all-reduce", 45, 60)],
           1: [("a.1", "fusion", 10, 90)]}
    spans = [("call", 0, 5), ("wait", 5, 100), ("check", 32, 38)]
    return window_of(ops, spans)


def test_busy_is_the_union_of_intervals():
    r = _hand_made()
    assert isinstance(r, Reduced)
    assert r.window_s == pytest.approx(100e-9)
    # chip 0: [10, 30] and [40, 60] -> 40 ns; chip 1: 80 ns
    assert r.chip_busy_s(0) == pytest.approx(40e-9)
    assert r.busy_s == pytest.approx(60e-9)
    gaps = r.idle_gaps(0)
    assert [round(g * 1e9) for _, g in gaps] == [40, 10, 10]
    # the gap at 30 ns began inside "wait"; the narrowest open span wins
    assert r.host_activity(33) == "check"
    assert r.host_activity(2) == "call"


def test_stage_seconds_average_over_chips():
    r = _hand_made()
    assert r.seconds(lambda n, o: o == "fusion") == pytest.approx(
        (10 + 10 + 80) * 1e-9 / 2)
    assert r.seconds(lambda n, o: o == "all-reduce", chip=0) == \
        pytest.approx(15e-9)


def test_a_trace_without_the_harness_spans_is_refused():
    with pytest.raises(ValueError):
        window_of({0: []}, [("check", 0, 1)])


def test_glue_leaves_out_every_stage_reader_in_the_spec():
    from types import SimpleNamespace
    glue = Bench().reader("glue_ms")
    r = _hand_made()
    run = SimpleNamespace(trace=r, calls=[None],
                          stages={"x": lambda n, o: o == "all-reduce"})
    # chip 0: 20 + 15 + 10 ns of glue, chip 1: 80; averaged, per call
    assert glue.read(run) == pytest.approx((10 + 15 + 10 + 80) / 2 * 1e-6)
    run.stages["y"] = lambda n, o: o == "fusion"
    assert glue.read(run) == pytest.approx(15 / 2 * 1e-6)


def test_stage_readers_are_found_by_their_spec_entry(tmp_path):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(Bench().stages()) == {
        m["name"] for m in spec["per_layer"]
        if hasattr(Bench().reader(m["name"]), "match")}
    assert "glue_ms" not in Bench().stages()
    spec["per_layer"].append({"name": "collective_ms", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "transport", "moves": "fp64_tflops"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    stages = Bench(str(tmp_path / "BENCHMARK.json")).stages()
    assert stages["collective_ms"]("psum.6", "all-reduce")
