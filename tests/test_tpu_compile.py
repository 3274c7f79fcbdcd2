"""Compile the main path for a TPU v5e that is described, not attached.

XLA:TPU and Mosaic are installed with jax, so every kernel of the DGEMM
path and the front-door routes built from them are compiled here at real
widths with ``interpret=False``. What the chip's compiler refuses — a
block layout, an int8 multiply, a 64-bit bitcast, a program larger than
HBM — fails here, at no chip time. Nothing runs: these tests say nothing
about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

N = 4096                       # kernel operands: 4096 x 4096
S = 9                          # the paper's INT8x9 operating point
W = 7
HBM_BYTES = 16 * 2 ** 30       # one v5e
SPEC = "ozaki-fp64x9/pallas_fused+epilogue"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _kernel_case(name, shape):
    """(callable, argument shapes) for one kernel, ``interpret=False``."""
    from repro.kernels import ops
    i8, f32, i32 = jnp.int8, jnp.float32, jnp.int32
    mat = (N, N)
    scale = 2.0 ** (-4 * W)
    if name == "int8_matmul_nt":
        return (lambda a, b: ops.int8_matmul_nt(a, b, interpret=False),
                [shape(mat, i8), shape(mat, i8)])
    if name == "int8_matmul_nt_batched":
        return (lambda a, b: ops.int8_matmul_nt_batched(a, b,
                                                        interpret=False),
                [shape((2,) + mat, i8), shape((2,) + mat, i8)])
    if name == "fused_split_dw":
        return (lambda hi, lo, e: ops.fused_split_dw(
                    hi, lo, e, num_splits=S, w=W, interpret=False),
                [shape(mat, f32), shape(mat, f32), shape((N,), i32)])
    if name == "int8_matmul_nt_epilogue_dw":
        return (lambda a, b, ch, cl: ops.int8_matmul_nt_epilogue_dw(
                    a, b, ch, cl, p_lo=0, t=S - 1, npairs=S, scale=scale,
                    interpret=False),
                [shape((S,) + mat, i8), shape((S,) + mat, i8),
                 shape(mat, f32), shape(mat, f32)])
    if name == "int8_matmul_nt_streaming_dw":
        return (lambda ah, al, ae, bh, bl, be, ch, cl:
                ops.int8_matmul_nt_streaming_dw(
                    ah, al, ae, bh, bl, be, ch, cl, num_splits=S, p_lo=0,
                    t=S - 1, npairs=S, w=W, scale=scale, interpret=False),
                [shape(mat, f32), shape(mat, f32), shape((N,), i32),
                 shape(mat, f32), shape(mat, f32), shape((N,), i32),
                 shape(mat, f32), shape(mat, f32)])
    if name == "accum_scaled_dw":
        return (lambda p, ch, cl: ops.accum_scaled_dw(
                    p, ch, cl, scale=scale, interpret=False),
                [shape(mat, i32), shape(mat, f32), shape(mat, f32)])
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "int8_matmul_nt", "int8_matmul_nt_batched", "fused_split_dw",
    "int8_matmul_nt_epilogue_dw", "int8_matmul_nt_streaming_dw",
    "accum_scaled_dw"])
def test_kernel_lowers_to_mosaic(shape, name):
    fn, args = _kernel_case(name, shape)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n,k", [(16384, 16384, 16384), (4096, 4096, 4096),
                                   (16384, 16384, 256)])
def test_epilogue_lowers_at_cell_blocks(shape, m, n, k):
    """The epilogue GEMM at the blocks ``epilogue_blocks`` picks for each
    chip benchmark cell's shape (square16k, serve4k with its batch folded
    into rows, rank256): Mosaic takes the block layout within the scoped
    VMEM limit the launch declares, and the kernel's code, which sits in
    HBM once per launch, stays within a few times that of 256 x 256 x 512
    blocks (the body runs in pieces; unrolled it was 15 times as large)."""
    from repro.kernels import launch, ops
    blocks = launch.epilogue_blocks(m, n, k, planes=2, el_bytes=4)
    limit = launch.epilogue_vmem_limit(*blocks, planes=2, el_bytes=4)
    assert limit == launch.EPILOGUE_VMEM_LIMIT
    i8, f32 = jnp.int8, jnp.float32
    args = (shape((S, m, k), i8), shape((S, n, k), i8), shape((m, n), f32),
            shape((m, n), f32))

    def lower(**blocks):
        return jax.jit(lambda a, b, ch, cl: ops.int8_matmul_nt_epilogue_dw(
            a, b, ch, cl, p_lo=0, t=S - 1, npairs=S, scale=2.0 ** (-10 * W),
            interpret=False, **blocks)).lower(*args)

    lowered = lower()
    # the declared limit rides in the kernel call's backend config
    assert f"\\22size\\22: {limit}" in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    small = lower(bm=256, bn=256, bk=512).compile()
    code = [c.memory_analysis().generated_code_size_in_bytes
            for c in (compiled, small)]
    assert code[0] <= 4 * code[1], code


def _program_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes +
            ma.temp_size_in_bytes)


@pytest.mark.parametrize("case", ["gemm", "batched"])
def test_front_door_dw_route_fits_one_chip(shape, monkeypatch, case):
    """``repro.matmul`` on DW operands at the ``configs.ozimmu_gemm``
    deployments: every stage a Mosaic kernel, the program inside HBM."""
    import repro
    from repro.configs.ozimmu_gemm import BATCHED_CONFIG, CONFIG
    from repro.core.xmath import DW
    from repro.kernels import ops
    monkeypatch.setattr(ops, "INTERPRET", False)
    if case == "gemm":
        a_dims, b_dims = (CONFIG.m, CONFIG.k), (CONFIG.k, CONFIG.n)
    else:
        c = BATCHED_CONFIG
        a_dims, b_dims = (c.batch, c.m, c.k), (c.k, c.n)
    f32 = jnp.float32
    fn = jax.jit(lambda ah, al, bh, bl: repro.matmul(
        DW(ah, al), DW(bh, bl), precision=SPEC))
    compiled = fn.lower(shape(a_dims, f32), shape(a_dims, f32),
                        shape(b_dims, f32), shape(b_dims, f32)).compile()
    text = compiled.as_text()
    # one split launch per operand plus one epilogue launch per
    # anti-diagonal group: nothing on the path is left to XLA
    assert text.count("tpu_custom_call") >= 2 + S
    assert _program_bytes(compiled) < HBM_BYTES


def test_f64_xla_route_compiles_without_64bit_bitcast(shape):
    """The float64 front door (``xla`` backend) lowers on XLA:TPU: the
    exponent reads and power-of-two scales go through ``core.xmath``,
    not through ``frexp``/``ldexp``'s f64 -> s64 bitcast."""
    import repro
    f64 = jnp.float64
    fn = jax.jit(lambda a, b: repro.matmul(a, b, precision="ozaki-fp64x3"))
    compiled = fn.lower(shape((512, 512), f64),
                        shape((512, 512), f64)).compile()
    assert _program_bytes(compiled) < HBM_BYTES
