"""Benchmark aggregator: one section per paper figure/table.

``PYTHONPATH=src python -m benchmarks.run`` prints
``name,us_per_call,derived,plan,policy`` CSV rows for every benchmark;
section mapping lives in DESIGN.md §5 and EXPERIMENTS.md.

``--policy SPEC`` pins a run-wide ``repro.api.MatmulPolicy`` (one front
door for backend/fusion/splits/target/fast-mode; a spec naming
``|cache=PATH`` / ``|autotune`` maps onto the same machinery as the
dedicated flags below) and the resolved spec string is recorded in the
``policy`` column of every row. ``--plan-cache PATH`` routes every
planned GEMM through a persistent ``core.autotune.PlanCache`` and
``--autotune`` measures candidates on misses — the chosen plan lands in
the ``plan`` CSV column of each row it applies to, so perf numbers are
reproducible from the row alone. The flags reach every registered
benchmark through ``common.CONTEXT``.
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)   # FP64 oracle + DGEMM baseline


def main(argv=None) -> None:
    from . import common

    ap = argparse.ArgumentParser(description=__doc__)
    common.add_plan_args(ap)
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    common.configure_from_args(args)

    print(common.CSV_HEADER)
    from . import (bench_distributed, bench_fig4_analytic,
                   bench_fig6_accuracy, bench_fig7_zerocancel,
                   bench_fig8_throughput, bench_fused_pipeline,
                   bench_quantum_sim, bench_scheme2, bench_serve_latency)
    bench_fig4_analytic.run()
    bench_fig6_accuracy.run()
    bench_fig7_zerocancel.run()
    bench_fig8_throughput.run()
    bench_fused_pipeline.run()
    bench_quantum_sim.run()
    bench_scheme2.run()
    bench_serve_latency.run()
    bench_distributed.run()
    if common.CONTEXT.plan_cache is not None:
        common.CONTEXT.plan_cache.save()


if __name__ == "__main__":
    main()
