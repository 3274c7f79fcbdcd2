"""Pallas kernel sweeps vs the pure-jnp oracles (interpret=True on CPU).

Integer paths assert exact equality; the df32 accumulation path is exact
too (identical compensated-arithmetic sequence).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executors import EpilogueExecutor, XlaExecutor
from repro.core.splitting import (SplitResult, row_exponents, split_int,
                                  split_int_dw)
from repro.core.tuning import PipelinePlan
from repro.core.xmath import DW, df32_from_f64
from repro.kernels import launch, ref
from repro.kernels.int8_gemm import (int8_matmul_nt, int8_matmul_nt_batched,
                                     int8_matmul_nt_epilogue_dw,
                                     int8_matmul_nt_epilogue_sw)
from repro.kernels.ozaki_accum import accum_scaled_dw, accum_scaled_sw
from repro.kernels.ozaki_split import fused_split_dw


@pytest.mark.parametrize("m,n,k", [
    (8, 8, 8), (16, 24, 32), (128, 64, 256), (200, 120, 530),
    (256, 256, 512), (33, 7, 129)])
def test_int8_gemm_sweep(rng, m, n, k):
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    bt = jnp.asarray(rng.integers(-128, 128, (n, k)), jnp.int8)
    got = np.asarray(int8_matmul_nt(a, bt, interpret=True))
    want = np.asarray(ref.int8_matmul_nt_ref(a, bt))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 64), (256, 256, 512)])
def test_int8_gemm_block_shapes(rng, bm, bn, bk):
    a = jnp.asarray(rng.integers(-128, 128, (100, 300)), jnp.int8)
    bt = jnp.asarray(rng.integers(-128, 128, (70, 300)), jnp.int8)
    got = np.asarray(int8_matmul_nt(a, bt, bm=bm, bn=bn, bk=bk,
                                    interpret=True))
    np.testing.assert_array_equal(got,
                                  np.asarray(ref.int8_matmul_nt_ref(a, bt)))


@pytest.mark.parametrize("m,k,s,w", [
    (8, 128, 9, 7), (64, 256, 13, 7), (100, 130, 5, 6), (16, 512, 3, 7)])
def test_fused_split_sweep(rng, m, k, s, w):
    x = jnp.asarray(rng.uniform(-0.5, 0.5, (m, k))
                    * np.exp(rng.standard_normal((m, k))))
    dw = df32_from_f64(x)
    exp = row_exponents(dw.hi)
    got = np.asarray(fused_split_dw(dw.hi, dw.lo, exp, num_splits=s, w=w,
                                    interpret=True))
    want = np.asarray(ref.fused_split_dw_ref(dw.hi, dw.lo, exp,
                                             num_splits=s, w=w))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,n,scale_pow", [(16, 128, -14), (100, 200, -28),
                                           (256, 256, -42)])
def test_accum_scaled_sweep(rng, m, n, scale_pow):
    p = jnp.asarray(rng.integers(-2 ** 30, 2 ** 30, (m, n)), jnp.int32)
    c_hi = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    c_lo = jnp.asarray(rng.standard_normal((m, n)) * 1e-8, jnp.float32)
    scale = float(2.0 ** scale_pow)
    gh, gl = accum_scaled_dw(p, c_hi, c_lo, scale=scale, interpret=True)
    wh, wl = ref.accum_scaled_dw_ref(p, c_hi, c_lo, scale=scale)
    np.testing.assert_array_equal(np.asarray(gh), np.asarray(wh))
    np.testing.assert_array_equal(np.asarray(gl), np.asarray(wl))


@pytest.mark.parametrize("b,m,n,k", [
    (1, 8, 8, 8), (3, 16, 24, 32), (2, 100, 60, 130)])
def test_int8_gemm_batched_sweep(rng, b, m, n, k):
    a = jnp.asarray(rng.integers(-128, 128, (b, m, k)), jnp.int8)
    bt = jnp.asarray(rng.integers(-128, 128, (b, n, k)), jnp.int8)
    got = np.asarray(int8_matmul_nt_batched(a, bt, interpret=True))
    want = np.asarray(ref.int8_matmul_nt_batched_ref(a, bt))
    np.testing.assert_array_equal(got, want)


def test_int8_gemm_batched_matches_unbatched(rng):
    a = jnp.asarray(rng.integers(-128, 128, (4, 32, 64)), jnp.int8)
    bt = jnp.asarray(rng.integers(-128, 128, (4, 16, 64)), jnp.int8)
    got = np.asarray(int8_matmul_nt_batched(a, bt, interpret=True))
    for i in range(4):
        np.testing.assert_array_equal(
            got[i], np.asarray(int8_matmul_nt(a[i], bt[i], interpret=True)))


@pytest.mark.parametrize("m,n,scale_pow", [(16, 128, -14), (100, 200, -28)])
def test_accum_scaled_sw_sweep(rng, m, n, scale_pow):
    p = jnp.asarray(rng.integers(-2 ** 30, 2 ** 30, (m, n)), jnp.int32)
    c = jnp.asarray(rng.standard_normal((m, n)), jnp.float64)
    scale = float(2.0 ** scale_pow)
    got = accum_scaled_sw(p, c, scale=scale, interpret=True)
    want = ref.accum_scaled_sw_ref(p, c, scale=scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,s,w", [(8, 128, 9, 7), (33, 130, 13, 6)])
def test_fused_split_f64_zero_lo_equals_split_int(rng, m, k, s, w):
    """(f64, 0) through the dw kernel == Algorithm 4 on the f64 matrix."""
    x = jnp.asarray(rng.uniform(-0.5, 0.5, (m, k))
                    * np.exp(rng.standard_normal((m, k))))
    want = split_int(x, s, w)
    got = fused_split_dw(x, jnp.zeros_like(x), want.exp, num_splits=s, w=w,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want.slices))


@pytest.mark.parametrize("m,n,k,p_lo,t,npairs", [
    (8, 8, 8, 0, 0, 1), (16, 24, 32, 1, 3, 2), (33, 7, 129, 0, 2, 3),
    (100, 60, 130, 2, 4, 2)])
def test_int8_gemm_epilogue_sw_sweep(rng, m, n, k, p_lo, t, npairs):
    """Epilogue-fused GEMM (single-word C) == GEMM kernel + scaled add."""
    s = 5
    a_sl = jnp.asarray(rng.integers(-100, 101, (s, m, k)), jnp.int8)
    b_sl = jnp.asarray(rng.integers(-100, 101, (s, n, k)), jnp.int8)
    c = jnp.asarray(rng.standard_normal((m, n)))
    scale = 2.0 ** -21
    got = int8_matmul_nt_epilogue_sw(a_sl, b_sl, c, p_lo=p_lo, t=t,
                                     npairs=npairs, scale=scale,
                                     interpret=True)
    p_t = sum(np.asarray(int8_matmul_nt(a_sl[p_lo + i],
                                        b_sl[t - p_lo - i], interpret=True))
              for i in range(npairs))
    want = np.asarray(c) + p_t.astype(np.float64) * scale
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("m,n,k,npairs", [(16, 24, 32, 2), (33, 7, 129, 3)])
def test_int8_gemm_epilogue_dw_matches_accum_kernel(rng, m, n, k, npairs):
    """Epilogue df32 add == ``accum_scaled_dw`` on the summed product."""
    s, p_lo, t = 4, 0, npairs - 1
    a_sl = jnp.asarray(rng.integers(-100, 101, (s, m, k)), jnp.int8)
    b_sl = jnp.asarray(rng.integers(-100, 101, (s, n, k)), jnp.int8)
    c_hi = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    c_lo = jnp.asarray(rng.standard_normal((m, n)) * 1e-8, jnp.float32)
    scale = 2.0 ** -28
    gh, gl = int8_matmul_nt_epilogue_dw(a_sl, b_sl, c_hi, c_lo, p_lo=p_lo,
                                        t=t, npairs=npairs, scale=scale,
                                        interpret=True)
    p_t = sum(np.asarray(int8_matmul_nt(a_sl[p_lo + i],
                                        b_sl[t - p_lo - i], interpret=True),
                         np.int64)
              for i in range(npairs)).astype(np.int32)
    wh, wl = accum_scaled_dw(jnp.asarray(p_t), c_hi, c_lo, scale=scale,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(gh), np.asarray(wh))
    np.testing.assert_array_equal(np.asarray(gl), np.asarray(wl))


def test_int8_gemm_epilogue_block_shapes(rng):
    """Explicit (small) blocks cover the multi-block epilogue grid walk."""
    s, m, n, k = 3, 70, 40, 200
    a_sl = jnp.asarray(rng.integers(-100, 101, (s, m, k)), jnp.int8)
    b_sl = jnp.asarray(rng.integers(-100, 101, (s, n, k)), jnp.int8)
    c = jnp.asarray(rng.standard_normal((m, n)))
    scale = 2.0 ** -14
    got = int8_matmul_nt_epilogue_sw(a_sl, b_sl, c, p_lo=0, t=2, npairs=3,
                                     scale=scale, bm=32, bn=128, bk=128,
                                     interpret=True)
    p_t = sum(np.asarray(int8_matmul_nt(a_sl[i], b_sl[2 - i],
                                        interpret=True))
              for i in range(3))
    want = np.asarray(c) + p_t.astype(np.float64) * scale
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("accum", ["df32", "f64"])
@pytest.mark.parametrize("batch", [None, 2])
@pytest.mark.parametrize("m,n,k", [(2048, 2048, 256), (600, 1100, 700)])
def test_int8_gemm_epilogue_recipe_blocks_match_xla(rng, accum, batch, m, n,
                                                    k):
    """With no block requested the epilogue launches take
    ``epilogue_blocks`` (several output tiles at 2048^2; one padded block
    at 600 x 1100 x 700), and the epilogue executor stays bitwise the
    XLA route: df32 (dw kernels) and f64 (sw), 2-D and batch-grid."""
    s, w = 3, 7
    lead = (s,) if batch is None else (s, batch)
    planes, el_bytes = (1, 8) if accum == "f64" else (2, 4)
    blocks = launch.epilogue_blocks(m, n, k, planes=planes,
                                    el_bytes=el_bytes)
    if m == 2048:       # blocks that divide, several output tiles
        assert all(d % b == 0 for d, b in zip((m, n, k), blocks))
        assert blocks[0] * blocks[1] < m * n
    else:               # one block, padded past the extents
        assert blocks == (608, 1152, 768)
    sa = SplitResult(jnp.asarray(rng.integers(-64, 65, lead + (m, k)),
                                 jnp.int8), None, w)
    sb = SplitResult(jnp.asarray(rng.integers(-64, 65, lead + (n, k)),
                                 jnp.int8), None, w)
    shape = lead[1:] + (m, n)
    e_base = jnp.asarray(rng.integers(-8, 8, shape), jnp.int32)
    outs = [ex(PipelinePlan(num_splits=s, backend=backend, fusion=fusion,
                            accum=accum, interpret=True)
               ).contract(sa, sb, w, e_base, shape)
            for ex, backend, fusion in ((XlaExecutor, "xla", "none"),
                                        (EpilogueExecutor, "pallas_fused",
                                         "epilogue"))]
    for want, got in zip(*(jax.tree.leaves(o) for o in outs)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# cell shapes (m, n, k): dgemm.square16k, batched.serve4k (batch folded
# into rows), dgemm.rank256
CELL_SHAPES = [(16384, 16384, 16384), (4096, 4096, 4096),
               (16384, 16384, 256)]


@pytest.mark.parametrize("m,n,k", CELL_SHAPES)
def test_epilogue_blocks_fit_and_divide_at_cell_shapes(m, n, k):
    blocks = launch.epilogue_blocks(m, n, k, planes=2, el_bytes=4)
    ws = launch.epilogue_working_set(*blocks, planes=2, el_bytes=4)
    assert ws <= launch.EPILOGUE_VMEM_LIMIT
    assert all(d % b == 0 for d, b in zip((m, n, k), blocks)), blocks
    assert blocks[:2] == (1024, 1024)
    # the launch declares the limit its working set was fitted to
    assert ws > launch.VMEM_BYTES
    assert launch.epilogue_vmem_limit(*blocks, planes=2, el_bytes=4) == \
        launch.EPILOGUE_VMEM_LIMIT


def test_epilogue_blocks_shrink_to_small_extents():
    assert launch.epilogue_blocks(8, 8, 1, planes=2, el_bytes=4) == \
        (32, 128, 128)
    assert launch.epilogue_blocks(600, 1100, 700, planes=1, el_bytes=8) == \
        (608, 1152, 768)
    assert launch.epilogue_vmem_limit(32, 128, 128, planes=2,
                                      el_bytes=4) is None


def test_epilogue_blocks_only_shrink_a_request():
    m = n = k = 16384
    assert launch.epilogue_blocks(m, n, k, 256, 256, 512, planes=2,
                                  el_bytes=4) == (256, 256, 512)
    # a request past the VMEM limit halves: the output tile first
    bm, bn, bk = launch.epilogue_blocks(m, n, k, 4096, 4096, 4096,
                                        planes=2, el_bytes=4)
    assert (bm, bn) == (1024, 1024)
    assert launch.epilogue_working_set(bm, bn, bk, planes=2, el_bytes=4) \
        <= launch.EPILOGUE_VMEM_LIMIT


# The other GEMM families launch what they launched before the epilogue
# got its own recipe: (shape, gemm_blocks, streaming_blocks at s = 9 and
# df32 words, crt_blocks at 18 moduli), with no block requested.
OTHER_FAMILIES = [
    ((8, 8, 1), (32, 128, 128), (32, 128, 128), (32, 128, 128)),
    ((100, 130, 530), (128, 256, 512), (128, 256, 512), (128, 256, 512)),
    ((600, 1100, 700), (256, 256, 512), (256, 256, 256), (256, 256, 512)),
    ((4096, 4096, 4096), (256, 256, 512), (256, 256, 256), (256, 256, 512)),
    ((16384, 16384, 256), (256, 256, 256), (256, 256, 256), (256, 256, 256)),
    ((16384, 16384, 16384), (256, 256, 512), (256, 256, 256),
     (256, 256, 512)),
]


@pytest.mark.parametrize("shape,gemm,streaming,crt", OTHER_FAMILIES)
def test_other_gemm_families_keep_their_blocks(shape, gemm, streaming, crt):
    assert launch.gemm_blocks(*shape) == gemm
    assert launch.gemm_blocks(*shape, *launch.GEMM_BLOCKS) == gemm
    assert launch.streaming_blocks(*shape, None, None, None, num_splits_a=9,
                                   num_splits_b=9, el_bytes=4) == streaming
    assert launch.crt_blocks(*shape, None, None, None, ell=18) == crt


def test_int8_gemm_jit_composes(rng):
    """Kernels must be callable under an outer jit (pjit path)."""
    a = jnp.asarray(rng.integers(-128, 128, (64, 128)), jnp.int8)
    bt = jnp.asarray(rng.integers(-128, 128, (32, 128)), jnp.int8)

    @jax.jit
    def f(a, bt):
        return int8_matmul_nt(a, bt, interpret=True) + 1

    got = np.asarray(f(a, bt))
    np.testing.assert_array_equal(
        got, np.asarray(ref.int8_matmul_nt_ref(a, bt)) + 1)
