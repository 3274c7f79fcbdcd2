#!/usr/bin/env python3
"""Smoke test of the Ozaki DGEMM on a TPU, through ``repro.matmul``.

    python chip_smoke.py              # one chip: device, dgemm, batched
    python chip_smoke.py --chips 4    # four chips: the k-shard phase only

Phases:

* ``device``  — platform, device kind and count, and a float64 probe:
  does ``1 + 2^-52`` survive, is scaling by ``2^±k`` exact, does a
  53-bit value survive host -> device -> host, do add and multiply
  round like IEEE binary64.
* ``dgemm``   — the ``configs.ozimmu_gemm.CONFIG`` deployment: 16384^3,
  phi = 1 operands (paper Eq. 6) split on the host into double-float32
  (DW) pairs, ``precision="ozaki-fp64x9/pallas_fused+epilogue"``. A
  block of the result is checked against a double-double reference and
  the df32 route's guaranteed bound. Then the float64 front door (run
  and bound-checked where ``core.xmath.native_f64()`` allows f64 routes,
  otherwise checked to refuse) and XLA's own float64 ``dot`` as the
  baseline.
* ``batched`` — ``BATCHED_CONFIG``: (32, 128, 4096) @ (4096, 4096)
  broadcast weights, same route and same check.
* ``kshard``  — only with ``--chips 4`` (which runs no other phase): k
  sharded over a (1, 4) mesh, 8192 x 8192 x 32768, the int32-partial
  collective schedules against the one-chip result, with every chip
  checked to hold its shard. f64 operands where the backend's float64 is
  IEEE (``core.xmath.native_f64``), the df32 route otherwise.

Every check raises on failure, so a failed phase exits nonzero. The last
line of standard output is one JSON object naming the device. The
script refuses to run on any platform but ``tpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

SPEC = "ozaki-fp64x9/pallas_fused+epilogue"
BLOCK = 128          # rows x columns of each result checked on the host
KSHARD_MNK = (8192, 8192, 32768)     # 8192 of k per chip on four chips


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(phase: str, ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[{phase}] FAILED: {what}")


def f64_probe(seed: int) -> dict:
    """float64 arithmetic on the default device against NumPy's."""
    import jax
    import numpy as np

    from repro.core import xmath

    rng = np.random.default_rng(seed)
    add = jax.jit(lambda x, y: x + y)
    mul = jax.jit(lambda x, y: x * y)
    one_ulp = float(np.asarray(add(np.float64(1.0), np.float64(2.0 ** -52))))
    x = rng.integers(2 ** 52, 2 ** 53, 4096).astype(np.float64) * 2.0 ** -53
    y = rng.integers(2 ** 52, 2 ** 53, 4096).astype(np.float64) * 2.0 ** -53
    roundtrip = np.asarray(jax.device_put(x))
    scale_exact = {}
    for k in (-1000, -200, -1, 1, 200, 1000):
        got = np.asarray(jax.jit(lambda v, k=k: xmath.ldexp(v, k))(x))
        scale_exact[k] = bool(np.array_equal(got, np.ldexp(x, k)))
    prod = np.asarray(mul(x, y))
    summ = np.asarray(add(x, y * 2.0 ** -30))
    return {
        "one_plus_ulp": one_ulp,
        "one_plus_ulp_differs": one_ulp != 1.0,
        "roundtrip_53bit": bool(np.array_equal(roundtrip, x)),
        "scale_exact": scale_exact,
        "mul_ieee_frac": float(np.mean(prod == x * y)),
        "mul_max_relerr": float(np.max(np.abs(prod - x * y) / (x * y))),
        "add_ieee_frac": float(np.mean(summ == x + y * 2.0 ** -30)),
    }


def probe_is_ieee(p: dict) -> bool:
    return (p["one_plus_ulp_differs"] and p["roundtrip_53bit"]
            and all(p["scale_exact"].values())
            and p["mul_ieee_frac"] == 1.0 and p["add_ieee_frac"] == 1.0)


def to_dw(x):
    """Host split of float64 into a double-float32 pair; ``hi + lo`` is
    exactly representable in float64 (both words sit inside x's 53-bit
    window), so the reference multiplies exactly what the device sees."""
    import numpy as np
    hi = x.astype(np.float32)
    lo = (x - hi.astype(np.float64)).astype(np.float32)
    return hi, lo, hi.astype(np.float64) + lo.astype(np.float64)


def block_error(c_block, a_rows, b_cols) -> float:
    """Scaled error of one result block against a double-double
    reference, computed on the host CPU."""
    import jax

    from repro.core.accuracy import scaled_error
    from repro.core.xmath import dd_matmul_np
    ref_hi, ref_lo = dd_matmul_np(a_rows, b_cols)
    with jax.default_device(jax.devices("cpu")[0]):
        return scaled_error(c_block, ref_hi, a_rows, b_cols, ref_lo)


def dw_bound(k: int, spec: str = SPEC) -> float:
    from repro.api import MatmulPolicy
    from repro.core.accuracy import error_bound
    cfg = MatmulPolicy.parse(spec).ozaki_config(k, accum="df32")
    return error_bound(cfg.num_splits, cfg.width_for(k), k, accum="df32")


def run_dw(phase: str, a, b, rows, cols) -> None:
    """One DW product through the front door, timed, block-checked."""
    import jax
    import numpy as np

    import repro
    from repro.core.xmath import DW

    a_hi, a_lo, a_val = to_dw(a)
    b_hi, b_lo, b_val = to_dw(b)
    words = [jax.device_put(w) for w in (a_hi, a_lo, b_hi, b_lo)]
    del a_hi, a_lo, b_hi, b_lo
    # one program, as compiled in tests/test_tpu_compile.py; run eagerly,
    # stage by stage, a second 16384^3 call overran the 16 GiB of one v5e
    gemm = jax.jit(lambda ah, al, bh, bl: repro.matmul(
        DW(ah, al), DW(bh, bl), precision=SPEC))
    t0 = time.perf_counter()
    out = jax.block_until_ready(gemm(*words))
    first = time.perf_counter() - t0
    del out                    # two results do not fit beside the operands
    t0 = time.perf_counter()
    out = jax.block_until_ready(gemm(*words))
    warm = time.perf_counter() - t0
    del words
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    k = a.shape[-1]
    a2 = a_val.reshape(-1, k)
    hi = np.asarray(out.hi).reshape(-1, out.hi.shape[-1])
    lo = np.asarray(out.lo).reshape(-1, out.lo.shape[-1])
    del out
    c = hi[rows][:, cols].astype(np.float64) + lo[rows][:, cols]
    err = block_error(c, a2[rows], b_val[:, cols])
    bound = dw_bound(k)
    log(phase, shape=f"{a.shape}@{b.shape}", spec=SPEC,
        first_call_s=f"{first:.3f}", warm_call_s=f"{warm:.3f}",
        process_peak_hbm_bytes=peak,
        block=f"{len(rows)}x{len(cols)}", scaled_err=f"{err:.3e}",
        df32_bound=f"{bound:.3e}")
    check(phase, np.isfinite(hi).all() and np.isfinite(lo).all(),
          "non-finite output")
    check(phase, err <= bound, f"scaled error {err:.3e} > bound {bound:.3e}")


def device_header() -> None:
    import jax
    devs = jax.devices()
    log("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs))
    from repro.kernels import ops
    log("device", pallas_interpret=ops.INTERPRET)
    check("device", not ops.INTERPRET, "Pallas kernels would run interpreted")


def phase_device(seed: int) -> dict:
    device_header()
    probe = f64_probe(seed)
    for key, val in probe.items():
        log("device", **{f"f64_{key}": val})
    from repro.core.xmath import native_f64
    log("device", f64_ieee_binary64=probe_is_ieee(probe),
        f64_routes_enabled=native_f64())
    return probe


def phase_dgemm(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from benchmarks.common import phi_matrix
    from repro.configs.ozimmu_gemm import CONFIG

    rng = np.random.default_rng(seed)
    a = phi_matrix(rng, CONFIG.m, CONFIG.k, 1.0)
    b = phi_matrix(rng, CONFIG.k, CONFIG.n, 1.0)
    rows = np.sort(rng.choice(CONFIG.m, BLOCK, replace=False))
    cols = np.sort(rng.choice(CONFIG.n, BLOCK, replace=False))
    check("dgemm", CONFIG.backend == "pallas_fused" and
          CONFIG.num_splits == 9 and CONFIG.accum == "df32",
          f"SPEC no longer matches {CONFIG}")
    run_dw("dgemm", a, b, rows, cols)

    # the float64 front door: a guaranteed f64-level result where the
    # library runs f64 routes, else a refusal that names the DW route
    from repro.core.xmath import native_f64
    if native_f64():
        da, db = jax.device_put(a), jax.device_put(b)
        t0 = time.perf_counter()
        c = np.asarray(repro.matmul(da, db, precision="ozaki-fp64x9"))
        secs = time.perf_counter() - t0
        del da, db
        from repro.api import MatmulPolicy
        from repro.core.accuracy import error_bound
        cfg = MatmulPolicy.parse("ozaki-fp64x9").ozaki_config(CONFIG.k)
        bound = error_bound(9, cfg.width_for(CONFIG.k), CONFIG.k)
        err = block_error(c[rows][:, cols], a[rows], b[:, cols])
        log("dgemm", f64_front_door="ozaki-fp64x9", first_call_s=f"{secs:.3f}",
            scaled_err=f"{err:.3e}", f64_bound=f"{bound:.3e}")
        check("dgemm", err <= bound, f"f64 route error {err:.3e} > {bound:.3e}")
    else:
        small = jnp.zeros((8, 8), jnp.float64)
        try:
            repro.matmul(small, small, precision="ozaki-fp64x9")
        except TypeError as e:
            log("dgemm", f64_front_door="refused", reason=repr(str(e)[:160]))
            check("dgemm", "DW" in str(e), "refusal does not name the DW route")
        else:
            raise SystemExit("[dgemm] FAILED: f64 operands ran where "
                             "core.xmath.native_f64() refuses them")

    # XLA's own f64 dot, the baseline: 16384^3 does not fit one v5e, so
    # the leading 8192^3 block of the same operands
    n = 8192
    fa, fb = jax.device_put(a[:n, :n]), jax.device_put(b[:n, :n])
    dot = jax.jit(lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float64))
    t0 = time.perf_counter()
    c = np.asarray(dot(fa, fb))
    secs = time.perf_counter() - t0
    del fa, fb
    r = rows[rows < n][:64]
    q = cols[cols < n][:64]
    err = block_error(c[r][:, q], a[r, :n], b[:n][:, q])
    log("dgemm", xla_f64_dot=f"{n}^3", first_call_s=f"{secs:.3f}",
        scaled_err=f"{err:.3e}", finite=bool(np.isfinite(c).all()))


def phase_batched(seed: int) -> None:
    import numpy as np

    from benchmarks.common import phi_matrix
    from repro.configs.ozimmu_gemm import BATCHED_CONFIG as B

    rng = np.random.default_rng(seed + 1)
    a = phi_matrix(rng, B.batch * B.m, B.k, 1.0).reshape(B.batch, B.m, B.k)
    b = phi_matrix(rng, B.k, B.n, 1.0)
    rows = np.sort(rng.choice(B.batch * B.m, BLOCK, replace=False))
    cols = np.sort(rng.choice(B.n, BLOCK, replace=False))
    run_dw("batched", a, b, rows, cols)


def phase_kshard(seed: int) -> None:
    """k sharded over a (1, 4) mesh: the int8-partial schedules against
    the one-chip result."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.common import phi_matrix
    from repro.core.ozaki import OzakiConfig, ozaki_matmul, ozaki_matmul_dw
    from repro.core.xmath import DW
    from repro.launch.mesh import make_mesh_compat
    from repro.parallel.ozaki_shard import (distributed_ozaki_matmul,
                                            ozaki_matmul_kshard_auto)

    devs = jax.devices()
    check("kshard", len(devs) == 4, f"needs 4 chips, found {len(devs)}")
    mesh = make_mesh_compat((1, 4), ("data", "model"))
    m, n, k = KSHARD_MNK
    rng = np.random.default_rng(seed + 2)
    a = phi_matrix(rng, m, k, 1.0)
    b = phi_matrix(rng, k, n, 1.0)
    from repro.core.xmath import native_f64
    f64 = native_f64()
    if not f64:            # the df32 route: f32 operands, df32 result
        a = a.astype(np.float32)
        b = b.astype(np.float32)
    cfg = OzakiConfig(num_splits=9, accum="f64" if f64 else "df32")

    def words(c):
        return (np.asarray(c),) if f64 else (np.asarray(c.hi),
                                            np.asarray(c.lo))

    one = jax.devices()[0]
    t0 = time.perf_counter()
    a1, b1 = jax.device_put(a, one), jax.device_put(b, one)
    if f64:
        one_chip = jax.jit(lambda x, y: ozaki_matmul(x, y, cfg))
    else:
        one_chip = jax.jit(lambda x, y: ozaki_matmul_dw(
            DW(x, 0 * x), DW(y.T, 0 * y.T), cfg))
    ref = words(one_chip(a1, b1))
    del a1, b1
    log("kshard", route="one-chip", accum=cfg.accum,
        first_call_s=f"{time.perf_counter() - t0:.3f}")

    sa = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
    sb = jax.device_put(b, NamedSharding(mesh, P("model", None)))
    for name, arr in (("a", sa), ("b", sb)):
        held = sorted(s.device.id for s in arr.addressable_shards)
        shapes = {tuple(s.data.shape) for s in arr.addressable_shards}
        log("kshard", operand=name, shard_devices=held, shard_shapes=shapes)
        check("kshard", held == sorted(d.id for d in devs) and
              len(shapes) == 1, f"operand {name} is not spread over 4 chips")

    routes = [("int8/psum", lambda: distributed_ozaki_matmul(
                  sa, sb, mesh, cfg, axis="model", schedule="psum")),
              ("int8/reduce_scatter", lambda: distributed_ozaki_matmul(
                  sa, sb, mesh, cfg, axis="model", schedule="reduce_scatter"))]
    if f64:
        routes.append(("f64/gspmd", lambda: ozaki_matmul_kshard_auto(
            sa, sb, mesh, cfg, axis="model")))
    for name, fn in routes:
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        secs = time.perf_counter() - t0
        first = out if f64 else out.hi
        held = sorted(s.device.id for s in first.addressable_shards)
        got = words(out)
        same = all(np.array_equal(x, y) for x, y in zip(got, ref))
        log("kshard", route=name, first_call_s=f"{secs:.3f}",
            out_shard_devices=held, bitwise_equal_one_chip=same)
        check("kshard", held == sorted(d.id for d in devs),
              f"{name}: output not on all 4 chips")
        check("kshard", same, f"{name}: differs from the one-chip result")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, dgemm and batched phases; "
                         "4: the k-shard phase alone")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    log("setup", compile_cache=enable_compile_cache())

    t0 = time.perf_counter()
    if args.chips == 4:
        device_header()
        phase_kshard(args.seed)
    else:
        phase_device(args.seed)
        phase_dgemm(args.seed)
        phase_batched(args.seed)
    log("done", wall_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
