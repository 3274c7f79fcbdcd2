"""The library's front door on DW operands, one chip:
``jax.jit(lambda ...: repro.matmul(DW(..), DW(..), precision=SPEC))``.

Config keys: ``precision`` (the policy spec the caller passes).
Operands are ``(hi, lo)`` float32 pairs; 3-D activations against 2-D
weights fold the batch into rows inside the front door.
"""
from __future__ import annotations

import jax


def build(cell, devices):
    import repro
    from repro.api import MatmulPolicy
    from repro.core.ozaki import resolve_accuracy_config
    from repro.core.xmath import DW

    from benchmarks.chip.counts import Plan
    from benchmarks.chip.harness import Route, copy_sampler

    spec = cell.config["precision"]
    gemm = jax.jit(lambda ah, al, bh, bl: repro.matmul(
        DW(ah, al), DW(bh, bl), precision=spec))

    def plan(m, n, k):
        # the operating point the front door resolves for this k
        cfg = MatmulPolicy.parse(spec).ozaki_config(k, accum="df32")
        cfg = resolve_accuracy_config(cfg, k)
        return Plan(splits=cfg.num_splits, pairs=cfg.num_gemms,
                    groups=len(cfg.diagonals()))

    index, sample = copy_sampler(devices)
    return Route(
        call=lambda a, b: gemm(a[0], a[1], b[0], b[1]),
        lower=lambda a, b: gemm.lower(a[0], a[1], b[0], b[1]), sample=sample,
        index=index, plan=plan, shardings=None, copies=1, output="df32",
        word_bytes=8)
