"""Serving launcher: continuous-batching engine demo on a reduced config.

Submits a stream of randomized requests, drains the engine, and verifies
one request against the sequential reference generator.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --requests 6 --slots 3
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import init_model
from repro.serving.engine import (Request, ServingEngine,
                                  generate_sequential)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", metavar="SPEC", default=None,
                    help="matmul policy spec for this engine (the one "
                         "front door; repro.api.MatmulPolicy), e.g. "
                         "'ozaki-fp64@1e-25:fast/pallas_fused+epilogue"
                         "|cache=plans.json|autotune'; add "
                         "'|shard=model|comm=int8' for the int8-slice "
                         "collective transport on a mesh. Subsumes (and "
                         "cannot be combined with) --precision/"
                         "--target-error/--fast-mode; --plan-cache/"
                         "--autotune stay combinable and override the "
                         "spec's |cache=/|autotune sections")
    ap.add_argument("--precision", default=None,
                    choices=["bf16", "int8_quant", "ozaki_fp64"],
                    help="legacy: override cfg.matmul_precision only "
                         "(prefer --policy)")
    ap.add_argument("--plan-cache", metavar="PATH", default=None,
                    help="persistent PlanCache JSON the engine pre-warms "
                         "at startup (ozaki_fp64 only)")
    ap.add_argument("--autotune", action="store_true",
                    help="measure candidate plans for cache misses during "
                         "the startup pre-warm")
    ap.add_argument("--target-error", type=float, default=None,
                    help="accuracy target on the scaled error "
                         "(core.accuracy); lets the driver reduce the "
                         "split count per projection shape")
    ap.add_argument("--fast-mode", action="store_true",
                    help="truncate slice pairs to the minimal budget "
                         "meeting --target-error (or drop the last "
                         "anti-diagonal without one)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    if cfg.frontend == "audio":
        raise SystemExit("serve demo targets text archs; see tests for "
                         "audio decode coverage")
    params, _ = init_model(cfg, jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)

    if args.policy is not None and (args.precision or args.target_error
                                    or args.fast_mode):
        raise SystemExit("--policy subsumes --precision/--target-error/"
                         "--fast-mode; pass one or the other")
    if args.policy is not None:
        from repro.api import MatmulPolicy
        pol = MatmulPolicy.parse(args.policy)
        print(f"[serve] matmul policy: {pol.spec()}")
        engine = ServingEngine(cfg, params, num_slots=args.slots,
                               max_len=args.max_len, policy=pol,
                               plan_cache=args.plan_cache,
                               autotune_plans=args.autotune or None)
    else:
        engine = ServingEngine(cfg, params, num_slots=args.slots,
                               max_len=args.max_len,
                               matmul_precision=args.precision,
                               ozaki_target_error=args.target_error,
                               ozaki_fast_mode=args.fast_mode or None,
                               plan_cache=args.plan_cache,
                               autotune_plans=args.autotune or None)
    if engine.plan_cache is not None:
        print(f"[serve] plan cache pre-warmed: {len(engine.plan_cache)} "
              f"plans ({engine.plan_cache.path})")
    reqs = []
    for rid in range(args.requests):
        plen = int(rng.integers(4, 12))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        req = Request(rid, prompt, max_new_tokens=args.max_new)
        reqs.append(req)
        engine.submit(req)

    t0 = time.time()
    finished = engine.run_until_done()
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in finished)
    print(f"[serve] {len(finished)} requests, {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens / dt:.1f} tok/s) with "
          f"{engine._steps} batched decode ticks")

    ref = generate_sequential(engine.cfg, params, reqs[0].prompt,
                              reqs[0].max_new_tokens,
                              max_len=args.max_len)
    got = next(r for r in finished if r.rid == 0).generated
    assert got == ref, (got, ref)
    print("[serve] continuous-batching output == sequential reference ✓")


if __name__ == "__main__":
    main()
