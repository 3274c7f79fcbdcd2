"""A whole run on the CPU, past the harness's look for a chip, with the
timed path sound, replaced by the control, or broken underneath:
``correct`` has to read true only for the sound program.

The control is the plain product in float32 (``entries/control_f32``).
The faults: a call that returns an earlier call's product (state left
unchanged), half of the batch left out, an answer altered where it is
produced (the DW low words dropped), and, on four host devices, the
exchange between chips left out."""
import math
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmarks.chip.harness import CHIP_DIR, ROOT, Bench, run_cell

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")
SEED = 3_000_000_011


@pytest.fixture(scope="module")
def bench():
    return Bench(os.path.join(FIXTURE, "BENCHMARK.json"),
                 dirs=(FIXTURE, CHIP_DIR))


def _run(bench, cell, **kw):
    return run_cell(bench, cell, SEED, 0.0, require_tpu=False, **kw)


@pytest.mark.parametrize("cell", ["tiny.swap", "tiny.serve"])
def test_sound_program_is_correct(bench, cell):
    r = _run(bench, cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 3
    assert r["compiles_in_window"] == 0
    assert list(r)[-1] == "checks"
    assert r["metrics"]["accuracy_bits"]["value"] > 40


def test_accuracy_bits_is_the_rms_of_the_checked_entries(bench):
    from types import SimpleNamespace
    read = bench.reader("accuracy_bits").read
    assert read(SimpleNamespace(rms_scaled_err=2.0 ** -40)) == 40.0
    assert read(SimpleNamespace(rms_scaled_err=float("inf"))) == 0.0
    r = _run(bench, "tiny.swap")
    # the root mean square lies at or under the largest entry's error
    assert r["metrics"]["accuracy_bits"]["value"] >= \
        -math.log2(r["checks"]["scaled_err"]["value"])


@pytest.mark.parametrize("cell,warm", [("tiny.serve", False),
                                       ("tiny.swap", True)])
def test_warm_up_runs_only_the_shape_pairs_the_first_call_left(bench, cell,
                                                               warm):
    r = _run(bench, cell)
    assert (r["setup"]["warmup_s"] > 0) == warm


@pytest.mark.parametrize("cell", ["tiny.swap", "tiny.serve"])
def test_peak_counts_the_programs_temporaries(bench, cell):
    r = _run(bench, cell)
    mem = r["memory"]
    assert mem["temp_bytes"] > 0
    assert r["device"]["memory_peak_bytes"] == \
        mem["peak_bytes_in_use"] + mem["temp_bytes"]
    assert r["metrics"]["peak_hbm_gib"]["value"] == pytest.approx(
        r["device"]["memory_peak_bytes"] / 2 ** 30)


@pytest.mark.parametrize("cell", ["tiny.swap", "tiny.serve"])
def test_float32_control_is_not_correct(bench, cell):
    r = _run(bench, cell, entry="control_f32")
    assert not r["correct"]
    assert r["checks"]["scaled_err"]["value"] > 1e3 * \
        r["checks"]["scaled_err"]["limit"]


def _stale(call):
    first = []

    def broken(a, b):
        out = call(a, b)
        if not first:
            first.append(out)
        return first[0]
    return broken


def _low_words_dropped(call):
    def broken(a, b):
        out = call(a, b)
        return type(out)(out.hi, jnp.zeros_like(out.lo))
    return broken


def _half_batch(call):
    def broken(a, b):
        out = call(a, b)
        keep = out.hi.shape[0] // 2
        return type(out)(*(x.at[keep:].set(0) for x in out))
    return broken


@pytest.mark.parametrize("cell,fault", [
    ("tiny.swap", _stale), ("tiny.serve", _stale),
    ("tiny.swap", _low_words_dropped), ("tiny.serve", _low_words_dropped),
    ("tiny.serve", _half_batch)])
def test_broken_timed_path_is_not_correct(bench, cell, fault):
    r = _run(bench, cell, wrap=fault)
    assert not r["correct"], r["checks"]
    assert r["failed"] >= 1


KSHARD = r"""
import os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax, jax.numpy as jnp
from benchmarks.chip.harness import CHIP_DIR, Bench, run_cell
bench = Bench(os.path.join({fixture!r}, "BENCHMARK.json"),
              dirs=({fixture!r}, CHIP_DIR))

def no_exchange(call):
    # every chip keeps only the first chip's share of k: the product
    # each chip would hold with the all-reduce left out
    def broken(a, b):
        k = a.shape[1]
        mask = (jnp.arange(k) < k // 4).astype(a.dtype)
        return call(a * mask[None, :], b)
    return broken

for label, kw in (("sound", {{}}), ("control", {{"entry": "control_f32"}}),
                  ("no_exchange", {{"wrap": no_exchange}})):
    r = run_cell(bench, "tiny.kshard", {seed}, 0.0, require_tpu=False, **kw)
    print(label, r["correct"], r["checks"]["copies_missing"]["value"],
          r["checks"]["scaled_err"]["value"])
"""


def test_kshard_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = KSHARD.format(root=ROOT, fixture=FIXTURE, seed=SEED)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    got = {line.split()[0]: line.split()[1:] for line in
           r.stdout.splitlines() if line.split()}
    assert got["sound"][:2] == ["True", "0"], got
    assert got["control"][0] == "False", got
    assert got["no_exchange"][0] == "False", got
