"""Operation and byte counts against hand counts, the peak table, and the
plan the front door resolves."""
import pytest

from benchmarks.chip.counts import Plan, call_counts, roofline_seconds
from benchmarks.chip.peaks import peaks_for

S9 = Plan(splits=9, pairs=45, groups=9)


def test_s9_keeps_45_pairs_in_9_groups():
    # the paper's schedule: pairs (i, j) with i + j <= s + 1
    pairs = [(i, j) for i in range(1, 10) for j in range(1, 10) if i + j <= 10]
    assert len(pairs) == S9.pairs
    assert len({i + j for i, j in pairs}) == S9.groups


def test_square16k_counts_by_hand():
    n = 16384
    c = call_counts(n, n, n, S9)
    assert c.fp64_flops == 2 * n ** 3
    assert c.gemm_ops == 45 * 2 * n ** 3
    # nine int8 slices of A and of B read once, the df32 pair written once
    assert c.gemm_bytes == 9 * 2 * n * n + 8 * n * n
    assert c.split_bytes == (8 + 9) * 2 * n * n
    t, bound = roofline_seconds(c.gemm_ops, c.gemm_bytes,
                                peaks_for("TPU v5 lite"))
    assert bound == "compute"
    assert t == pytest.approx(45 * 2 * n ** 3 / 393e12)


def test_serve4k_and_rank256_do_the_same_int8_work():
    serve = call_counts(32 * 128, 4096, 4096, S9)
    rank = call_counts(16384, 16384, 256, S9)
    assert serve.gemm_ops == rank.gemm_ops == 45 * 2 * 4096 ** 3
    assert serve.fp64_flops == rank.fp64_flops
    # the rank-256 update writes a 16x larger output
    assert 8 * 16384 ** 2 == 16 * 8 * 4096 ** 2
    assert rank.gemm_bytes > serve.gemm_bytes


def test_kshard_counts_each_chips_share_of_k():
    c = call_counts(8192, 8192, 32768, S9, chips=4, word_bytes=4,
                    output="int32_groups")
    assert c.fp64_flops == 2 * 8192 * 8192 * 32768
    assert c.gemm_ops == 45 * 2 * 8192 ** 3
    assert c.gemm_bytes == 9 * 2 * 8192 * 8192 + 4 * 9 * 8192 * 8192
    with pytest.raises(ValueError):
        call_counts(8, 8, 30, S9, chips=4)


def test_peak_table_has_the_published_v5e_figures():
    p = peaks_for("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5", "cpu", ""])
def test_peak_table_refuses_an_unknown_device_kind(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for(kind)


@pytest.mark.parametrize("k", [256, 4096, 16384])
def test_front_door_resolves_s9_with_45_pairs(k):
    from benchmarks.chip.harness import Bench
    bench = Bench()
    cell = bench.cell("dgemm.square16k")
    route = bench.route(cell, "front_door", [_FakeDevice()])
    assert route.plan(64, 64, k) == S9


def test_kshard_resolves_s9_with_45_pairs():
    import os

    import jax
    from benchmarks.chip.harness import CHIP_DIR, Bench
    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixture")
    bench = Bench(os.path.join(fixture, "BENCHMARK.json"),
                  dirs=(fixture, CHIP_DIR))
    cell = bench.cell("tiny.kshard")
    devs = jax.devices()[:1] * 4
    route = bench.route(cell, "kshard", devs)
    assert route.plan(8192, 8192, 32768) == S9


class _FakeDevice:
    id = 0
