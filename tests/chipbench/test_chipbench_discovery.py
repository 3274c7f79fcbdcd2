"""Cells, configurations, traffic, limits, entries and metrics are found
by name; a cell is added by files alone; ``BENCHMARK.json`` keeps to the
benchmark's contract."""
import json
import os
import re

import pytest

from benchmarks.chip.harness import CHIP_DIR, ROOT, Bench

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell):
    bench = Bench()
    c = bench.cell(cell)
    assert c.traffic["calls"] and c.traffic["operands"]
    for lhs, rhs in c.traffic["calls"]:
        assert c.traffic["operands"][lhs][-1] == c.traffic["operands"][rhs][0]
    assert c.limits["scaled_err"]["limit"] > 0
    assert hasattr(bench.module("entries", c.config["entry"]), "build")
    assert c.config["operands"]["kind"] in ("dw", "f32")


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["end_to_end"]
                                    + SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(Bench().reader(metric).read)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    bench = Bench()
    e2e = {m["name"] for m in bench.metrics(cell, trace=False)}
    layer = bench.metrics(cell, trace=True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in e2e


def test_a_cell_added_by_files_alone_is_found():
    bench = Bench(os.path.join(FIXTURE, "BENCHMARK.json"),
                  dirs=(FIXTURE, CHIP_DIR))
    for cell in ("tiny.swap", "tiny.serve"):
        c = bench.cell(cell)
        assert c.config["entry"] == "front_door"
        assert os.path.dirname(bench.find("traffic", c.name.replace(
            "tiny.", "tiny_"), ".json")) == os.path.join(FIXTURE, "traffic")
    # metrics the fixture names resolve to the benchmark's own readers
    assert bench.reader("fp64_tflops").read
    assert [m["name"] for m in bench.metrics("tiny.serve", False)] == [
        "fp64_tflops", "call_p95_ms", "accuracy_bits", "peak_hbm_gib",
        "setup_s"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        Bench().cell("no.such.cell")


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./\-]{1,200}$", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    files = [w for w in SPEC["command"] if "/" in w]
    assert files and all(any(f.startswith(p + "/") for p in SPEC["paths"])
                         for f in files)
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    metric_names = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in metric_names
        metric_names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m and m["moves"] in metric_names


def test_roofline_and_idle_metrics_are_named_as_the_contract_asks():
    for m in SPEC["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
