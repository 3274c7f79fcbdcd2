"""The check's samples held through a window (``harness.Held``): a seeded
reservoir of the earlier calls and the newest call, so that the device
holds at most ``check.calls`` samples between calls however long the
window runs, and the check reads ``min(check.calls, n)`` calls, the last
among them."""
import dataclasses
import os
import weakref
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import detail, harness
from benchmarks.chip.harness import CHIP_DIR, Bench, Held, run_cell

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")
SEED = 3_000_000_011
PULL = harness._pull


class Sample:
    """A stand-in for a sample, which a weak reference can follow."""


def _feed(held: Held, n: int) -> list:
    """Add ``n`` samples; return the number alive after each add."""
    refs, alive = [], []
    for _ in range(n):
        s = Sample()
        refs.append(weakref.ref(s))
        held.add(s)
        del s
        alive.append(sum(r() is not None for r in refs))
    return alive


@pytest.mark.parametrize("calls,n", [(3, 1), (3, 2), (3, 3), (3, 15),
                                     (8, 5), (8, 400), (1, 6)])
def test_held_keeps_at_most_calls_and_the_last(calls, n):
    held = Held(calls, SEED)
    alive = _feed(held, n)
    assert max(alive) <= calls
    picked = [i for i, _ in held.picked()]
    assert len(picked) == min(calls, n)
    assert picked == sorted(set(picked)) and picked[-1] == n - 1
    if n <= calls:
        assert picked == list(range(n))


def test_held_picks_follow_the_seed():
    def picks(seed, n):
        held = Held(8, seed)
        _feed(held, n)
        return [i for i, _ in held.picked()]
    assert picks(SEED, 400) == picks(SEED, 400)
    assert picks(SEED, 400) != picks(SEED + 1, 400)


def test_held_draws_the_earlier_calls_uniformly():
    # Algorithm R keeps each of the n - 1 earlier calls with chance
    # (calls - 1) / (n - 1): 3 / 9 here, +-0.0086 over 3000 seeds
    calls, n, seeds = 4, 10, 3000
    hits = np.zeros(n - 1)
    for seed in range(seeds):
        held = Held(calls, 2 ** 40 + seed)
        _feed(held, n)
        for i, _ in held.picked()[:-1]:
            hits[i] += 1
    assert np.all(np.abs(hits / seeds - (calls - 1) / (n - 1)) < 0.05)


# ---------------------------------------------------------------------------
# the harness's window and detail.py's, on a tiny cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench():
    return Bench(os.path.join(FIXTURE, "BENCHMARK.json"),
                 dirs=(FIXTURE, CHIP_DIR))


def _window(bench, monkeypatch, tmp_path, loop: str, calls: int) -> dict:
    """Run ``loop``'s window of ``tiny.serve`` for ``calls`` calls, on a
    clock that each timed call moves on by one second. Returns the
    samples alive at each new sample, and the calls the check read."""
    clock = [0.0]
    fake = SimpleNamespace(perf_counter=lambda: clock[0])
    monkeypatch.setattr(harness, "time", fake)
    monkeypatch.setattr(detail, "time", fake)
    refs, alive, checked = [], [], []

    def route(cell, entry, devices):
        r = Bench.route(bench, cell, entry, devices)

        def call(lhs, rhs):
            clock[0] += 1.0
            return r.call(lhs, rhs)

        def sample(out, idx):
            s = r.sample(out, idx)
            refs.extend(weakref.ref(hi) for hi, _ in s)
            alive.append(sum(ref() is not None for ref in refs))
            return s

        return dataclasses.replace(r, call=call, sample=sample)

    def recorded_pull(ops, cycle, host_idx, picked, which):
        checked.extend(i for i, _ in picked)
        return PULL(ops, cycle, host_idx, picked, which)

    monkeypatch.setattr(bench, "route", route)
    monkeypatch.setattr(harness, "_pull", recorded_pull)
    if loop == "harness":
        r = run_cell(bench, "tiny.serve", SEED, float(calls),
                     require_tpu=False)
        assert r["correct"], r["checks"]
        assert r["attempted"] == calls
        assert r["calls_checked"] == len(checked)
    else:
        detail.traced_window(bench, "tiny.serve", SEED, float(calls),
                             str(tmp_path / "trace"), require_tpu=False)
    return {"alive": alive, "checked": checked}


@pytest.mark.parametrize("loop", ["harness", "detail"])
def test_a_long_window_holds_at_most_calls_plus_one_samples(
        bench, monkeypatch, tmp_path, loop):
    chk = bench.cell("tiny.serve").traffic["check"]["calls"]
    w = _window(bench, monkeypatch, tmp_path, loop, 5 * chk)
    # each sample counted as it is made: the held ones and the new one
    assert len(w["alive"]) >= 5 * chk
    assert max(w["alive"]) <= chk + 1


@pytest.mark.parametrize("calls", [3, 15, 40])
def test_the_check_reads_the_held_calls_and_the_last(bench, monkeypatch,
                                                     tmp_path, calls):
    chk = bench.cell("tiny.serve").traffic["check"]["calls"]
    checked = _window(bench, monkeypatch, tmp_path, "harness",
                      calls)["checked"]
    assert len(checked) == min(chk, calls) == len(set(checked))
    assert checked[-1] == calls - 1
    if calls == chk:
        assert checked == list(range(calls))
    # the same seed and window pick the same calls
    assert _window(bench, monkeypatch, tmp_path, "harness",
                   calls)["checked"] == checked


def test_a_long_windows_picks_reach_past_its_first_calls(bench, monkeypatch,
                                                         tmp_path):
    chk = bench.cell("tiny.serve").traffic["check"]["calls"]
    checked = _window(bench, monkeypatch, tmp_path, "harness",
                      40)["checked"]
    assert max(checked[:-1]) >= chk
