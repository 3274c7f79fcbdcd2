"""parallel.compression: the SliceWire/ResidueWire transports (lossless)
and the EF-SGD int8 gradient compressor (lossy, error-bounded).

Single-device properties; the mesh behaviour lives in test_distributed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)


# ----------------------------------------------------------------------------
# SliceWire: pack/unpack are exact transposes; byte model matches reality
# ----------------------------------------------------------------------------

def _split(rows=12, k=40, s=7):
    from repro.core.splitting import slice_width, split_int
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows, k))
                    * np.exp(rng.integers(-10, 10, (rows, 1))))
    return split_int(x, s, slice_width(k)), x


def test_slice_wire_round_trip_exact():
    from repro.parallel.compression import pack_slices, unpack_slices
    sr, _ = _split()
    wire = pack_slices(sr)
    assert wire.slices.dtype == jnp.int8
    assert wire.slices.shape == (12, 7, 40)       # sharded dim leads
    back = unpack_slices(wire)
    assert np.array_equal(np.asarray(back.slices), np.asarray(sr.slices))
    assert np.array_equal(np.asarray(back.exp), np.asarray(sr.exp))
    assert back.w == sr.w


def test_slice_wire_byte_model_matches_arrays():
    from repro.parallel.compression import (pack_slices, slice_wire_bytes,
                                            wire_nbytes)
    sr, _ = _split(rows=12, k=40, s=7)
    wire = pack_slices(sr)
    assert wire_nbytes(wire) == slice_wire_bytes(12, 40, 7)
    # the headline economics: s bytes/element (+exp) vs 8 for f64
    assert slice_wire_bytes(12, 40, 7) < 8 * 12 * 40


def test_slice_wire_reconstructs_operand():
    """Lossless transport: the unpacked SplitResult reconstructs to the
    bitwise-identical value the un-wired split reconstructs to (the wire
    round-trip is pure transposes — zero arithmetic)."""
    from repro.core.splitting import reconstruct
    from repro.parallel.compression import pack_slices, unpack_slices
    sr, x = _split()
    back = unpack_slices(pack_slices(sr))
    assert np.array_equal(np.asarray(reconstruct(back)),
                          np.asarray(reconstruct(sr)))
    # and the kept part carries the top s*w mantissa bits of x
    rel = np.abs(np.asarray(reconstruct(sr)) - np.asarray(x))
    exp = np.asarray(sr.exp)
    assert (rel <= np.ldexp(1.0, exp - sr.w * 7 + 1)[:, None]).all()


# ----------------------------------------------------------------------------
# ResidueWire: the Scheme II sibling — same wire discipline, ell planes
# ----------------------------------------------------------------------------

def _residues(rows=12, k=40, s=5, ell=6):
    from repro.core.modular import residues_from_slices, usable_moduli
    from repro.core.splitting import split_int
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((rows, k))
                    * np.exp(rng.integers(-10, 10, (rows, 1))))
    sr = split_int(x, s, 7)
    moduli = usable_moduli(k)[:ell]
    return residues_from_slices(sr.slices, 7, moduli), sr.exp, moduli


def test_residue_wire_round_trip_exact():
    from repro.parallel.compression import pack_residues, unpack_residues
    res, exp, moduli = _residues()
    wire = pack_residues(res, exp, moduli)
    assert wire.residues.dtype == jnp.int8
    assert wire.residues.shape == (12, 6, 40)     # sharded dim leads
    assert wire.moduli == tuple(moduli)           # static metadata
    back, back_exp = unpack_residues(wire)
    assert np.array_equal(np.asarray(back), np.asarray(res))
    assert np.array_equal(np.asarray(back_exp), np.asarray(exp))


def test_residue_wire_byte_model_matches_arrays():
    from repro.parallel.compression import (pack_residues,
                                            residue_wire_bytes,
                                            slice_wire_bytes, wire_nbytes)
    res, exp, moduli = _residues(rows=12, k=40, s=5, ell=6)
    wire = pack_residues(res, exp, moduli)
    assert wire_nbytes(wire) == residue_wire_bytes(12, 40, 6)
    # the headline economics: ell bytes/element (+exp) vs 8 for f64
    assert residue_wire_bytes(12, 40, 6) < 8 * 12 * 40
    # cross-wire arbitration: the residue wire beats the slice wire
    # exactly when ell < s (the comm_bytes_model honesty rule)
    assert residue_wire_bytes(12, 40, 4) < slice_wire_bytes(12, 40, 5)
    assert residue_wire_bytes(12, 40, 6) > slice_wire_bytes(12, 40, 5)


def test_residue_wire_reconstruction_exact():
    """Wire-round-tripped residues feed the CRT pipeline to the bitwise-
    identical product: the transport is pure transposes, so the Garner
    digits — and hence the f64 reconstruction — cannot move a bit."""
    from repro.core.modular import (ModularConfig, center_mod, crt_digits,
                                    crt_value, ozaki2_matmul,
                                    residues_from_slices, usable_moduli)
    from repro.core.splitting import split_int
    from repro.parallel.compression import pack_residues, unpack_residues
    rng = np.random.default_rng(2)
    m, k, n = 8, 96, 10
    a = jnp.asarray(rng.standard_normal((m, k))
                    * np.exp(rng.integers(-8, 8, (m, 1))))
    b = jnp.asarray(rng.standard_normal((k, n)))
    cfg = ModularConfig()
    plan = cfg.plan(k)
    moduli = usable_moduli(k)[:plan.num_moduli]
    sa = split_int(a, plan.num_splits, cfg.w)
    sb = split_int(b.T, plan.num_splits, cfg.w)
    ra = residues_from_slices(sa.slices, cfg.w, moduli)
    rb = residues_from_slices(sb.slices, cfg.w, moduli)
    rb_wire, exp = unpack_residues(pack_residues(rb, sb.exp, moduli))
    from repro.core.executors import gemm_xla
    p = gemm_xla(ra, rb_wire)
    digits = crt_digits(center_mod(p, moduli), moduli)
    e_base = (sa.exp[:, None].astype(jnp.int32) +
              exp[None, :].astype(jnp.int32))
    c = crt_value(digits, moduli, plan.beta, e_base)
    assert np.array_equal(np.asarray(c),
                          np.asarray(ozaki2_matmul(a, b, cfg)))


def test_residue_wire_round_trip_property():
    pytest.importorskip("hypothesis",
                        reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.core.modular import usable_moduli
    from repro.parallel.compression import (pack_residues,
                                            residue_wire_bytes,
                                            unpack_residues, wire_nbytes)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2 ** 31), rows=st.integers(1, 9),
           k=st.integers(1, 33), ell=st.integers(1, 8))
    def prop(seed, rows, k, ell):
        moduli = usable_moduli(max(k, 1))[:ell]
        rng = np.random.default_rng(seed)
        halves = (np.asarray(moduli, np.int64)[:, None, None] - 1) // 2
        res = jnp.asarray(
            rng.integers(-halves, halves + 1, (len(moduli), rows, k)),
            jnp.int8)
        exp = jnp.asarray(rng.integers(-50, 50, (rows,)), jnp.int32)
        wire = pack_residues(res, exp, moduli)
        back, back_exp = unpack_residues(wire)
        assert np.array_equal(np.asarray(back), np.asarray(res))
        assert np.array_equal(np.asarray(back_exp), np.asarray(exp))
        assert wire_nbytes(wire) == residue_wire_bytes(rows, k,
                                                       len(moduli))

    prop()


# ----------------------------------------------------------------------------
# int8 quantizer: deterministic round-trip error bound
# ----------------------------------------------------------------------------

def test_quantize_dequantize_error_bound():
    from repro.parallel.compression import dequantize_int8, quantize_int8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((64, 33)), jnp.float32)
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    # round-to-nearest against a per-tensor scale: |err| <= scale/2
    assert err.max() <= float(scale) / 2 + 1e-12
    # zeros stay exactly zero (scale has the +eps guard, q = 0)
    qz, sz = quantize_int8(jnp.zeros((4, 4), jnp.float32))
    assert np.array_equal(np.asarray(qz), np.zeros((4, 4)))
    assert np.array_equal(np.asarray(dequantize_int8(qz, sz)),
                          np.zeros((4, 4)))


# ----------------------------------------------------------------------------
# EF-SGD: the residual stays bounded (error feedback does not accumulate)
# ----------------------------------------------------------------------------

def test_ef_residual_stays_bounded():
    """Per-round quantization error is <= scale/2 elementwise and the
    residual is exactly (input - quantized), so over T rounds with fresh
    gradients the residual never grows beyond one quantization step of
    the current round — the EF-SGD boundedness that makes the compressed
    sum converge (mesh-level convergence is covered in
    test_distributed)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh_compat
    from repro.parallel.compression import (EFState, compress_psum,
                                            init_ef_state)
    mesh = make_mesh_compat((1,), ("data",))      # axis of size 1: exact psum
    rng = np.random.default_rng(4)

    @jax.jit                  # one compile, not one per eager primitive
    def one_round(g, r):
        def local(g, r):
            avg, ef = compress_psum({"g": g}, EFState({"g": r}), "data")
            return avg["g"], ef.residual["g"]
        return jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)(g, r)

    g0 = jnp.asarray(rng.standard_normal(256), jnp.float32)
    res = jnp.asarray(init_ef_state({"g": g0}).residual["g"])
    for t in range(30):
        g = jnp.asarray(rng.standard_normal(256), jnp.float32) * (1 + t % 3)
        prev = res
        avg, res = one_round(g, res)
        # the quantizer's scale is max|g + prev_res| / 127; round-to-
        # nearest leaves at most half a step behind as the new residual
        bound = float(jnp.max(jnp.abs(g + prev))) / 127.0 / 2
        assert float(jnp.max(jnp.abs(res))) <= bound + 1e-6, t
