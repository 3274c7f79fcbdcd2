#!/usr/bin/env python3
"""Take one traced window of a cell and read it in detail.

    python3 benchmarks/chip/detail.py --workload batched.serve4k \\
        --seed 7 --seconds 4 [--fixture tests/chipbench/data/x.json]

The cell is set up as ``run.py`` sets it up (operands and sample index
sets from the seed, each shape pair warmed once) and called in the same
closed loop, with the same ``call`` / ``check`` / ``wait`` spans and
the cyclic collector off, for ``--seconds`` with the profiler on. The trace is kept and read whole (``idle.py``). The
last line of standard output is one JSON object, per call where it says
``_ms``:

* ``stages_ms``: device time of each stage, by the trace's own ``tf_op``,
  and ``stages_ms_hlo`` as the per-layer readers find it from the
  compiled programs (``scopes.py``); ``hlo_agreement`` the share of
  device time on which the two agree;
* ``glue_ms``: ``glue_ms``'s operations split by stage, and the unscoped
  rest by operation;
* ``idle_ms``: idle time by cause; ``idle_gaps``: the longest gaps,
  named by cause; ``clock_margins``;
* ``program_counters``: ``repro.tracing``'s counters per trace of the
  warm-up (plans, GEMM pairs and launches) beside ``counts.Plan``, and
  every counter's count inside the window, where each should read 0: a
  trace or a plan in the window means the program was traced again.

``--fixture`` writes two calls of the window, trimmed like
``tests/chipbench/data/serve4k_two_calls.json``, with each
operation's ``tf_op``, the runtime's host events and the programs'
instruction metadata. Exits 2 where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# calls of the window a fixture keeps
FIXTURE_CALLS = 2


def traced_window(bench, name: str, seed: int, seconds: float,
                  trace_dir: str, *, require_tpu: bool = True) -> dict:
    """Set cell ``name`` up and warm it as ``harness.run_cell`` does, then
    run its closed loop for ``seconds`` (and at least the calls the cell
    checks) with the profiler writing to ``trace_dir``. Returns the
    devices, route, (m, n, k) of each shape pair and the program's
    counters over the warm-up and over the window.

    The loop is ``run_cell``'s, less its timing and the check: the same
    operands, the same sample index sets rotating (call i of a product
    samples with set i mod ``POOL``), the same samples held (``Held``),
    the same spans, and Python's cyclic collector off in the window.
    ``tests/chipbench`` holds the two loops to the same sequence of
    calls."""
    import gc

    import jax
    import numpy as np
    from repro import tracing

    from benchmarks.chip.harness import (POOL, Held, _product, _shape_of,
                                         chips_for)
    from benchmarks.chip.operands import make_operands

    jax.config.update("jax_enable_x64", True)
    cell = bench.cell(name)
    devs = chips_for(cell, require_tpu)
    route = bench.route(cell, cell.config["entry"], devs)
    traffic = cell.traffic
    names = list(traffic["operands"])
    opnd = cell.config["operands"]
    arrays = make_operands(
        seed, [_shape_of(traffic, n) for n in names], opnd["phi"],
        opnd["kind"],
        None if route.shardings is None else [route.shardings[n]
                                              for n in names])
    ops = dict(zip(names, arrays))
    cycle = [tuple(c) for c in traffic["calls"]]
    chk = traffic["check"]
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 1])
    dev_idx = []
    for m, n, _ in (_product(traffic, *c) for c in cycle):
        dev_idx.append([route.index(
            np.sort(rng.choice(m, chk["rows"], replace=False)),
            np.sort(rng.choice(n, chk["cols"], replace=False)))
            for _ in range(POOL)])

    def delta(before):
        now = tracing.counters()
        return {k: now[k] - before[k] for k in now}

    shapes = {}
    for j, (lhs, rhs) in enumerate(cycle):
        shapes.setdefault((_shape_of(traffic, lhs), _shape_of(traffic, rhs)),
                          j)
    before = tracing.counters()
    for j in shapes.values():
        lhs, rhs = cycle[j]
        out = route.call(ops[lhs], ops[rhs])
        jax.block_until_ready((out, route.sample(out, dev_idx[j][0])))
        del out
    warm_counts = delta(before)

    shutil.rmtree(trace_dir, ignore_errors=True)
    before = tracing.counters()
    jax.profiler.start_trace(trace_dir)
    # the samples are held as the harness holds them for its check
    i, held = 0, Held(int(chk["calls"]), seed)
    gc.collect()
    gc.disable()
    try:
        deadline = time.perf_counter() + seconds
        while True:
            j = i % len(cycle)
            lhs, rhs = cycle[j]
            s = (i // len(cycle)) % POOL
            with jax.profiler.TraceAnnotation("call"):
                out = route.call(ops[lhs], ops[rhs])
            with jax.profiler.TraceAnnotation("check"):
                sample = route.sample(out, dev_idx[j][s])
            with jax.profiler.TraceAnnotation("wait"):
                jax.block_until_ready((out, sample))
            held.add(sample)
            del out, sample
            i += 1
            if time.perf_counter() >= deadline and i >= int(chk["calls"]):
                break
    finally:
        gc.enable()
    jax.profiler.stop_trace()
    return {"devices": devs, "route": route,
            "products": sorted({_product(traffic, *cycle[j])
                                for j in shapes.values()}),
            "warm_counts": warm_counts, "window_counts": delta(before)}


def _per_call(seconds: dict, calls: int) -> dict:
    return {str(k): v / calls * 1e3 for k, v in seconds.items()}


def read_window(bench, trace_dir: str, chips, programs) -> tuple:
    """``(report, detail)`` of a kept trace: the report's stages, glue,
    idle causes and margins, and the ``idle.Detail`` read."""
    from benchmarks.chip import idle, scopes

    detail = idle.read_trace_json(idle.find_trace_json(trace_dir), chips)
    reduced = detail.reduced()
    chip = min(chips)
    calls = reduced.calls
    args = frozenset().union(*(p.args for p in programs if p.scoped))
    t0, t1 = reduced.start, reduced.end
    window = [e for e in detail.ops[chip] if e[3] > t0 and e[2] < t1]
    tf = [scopes.stage_of(e[4], args) for e in window]
    ran = scopes.attribute([e[0] for e in window], programs)
    hlo = [p.stage(e[0]) if p is not None else None
           for e, p in zip(window, ran)]
    matches = list(bench.stages().values())
    by_tf, by_hlo, glue, unscoped = {}, {}, {}, {}
    agree = total = 0.0
    for e, st, sh, p in zip(window, tf, hlo, ran):
        dt = (min(e[3], t1) - max(e[2], t0)) * 1e-9
        by_tf[st] = by_tf.get(st, 0.0) + dt
        by_hlo[sh] = by_hlo.get(sh, 0.0) + dt
        total += dt
        agree += dt if st == sh else 0.0
        if not any(f(e[0], e[1]) for f in matches):
            glue[st] = glue.get(st, 0.0) + dt
            if st is None:
                key = f"{p.name if p else '?'}:{e[0]}"
                unscoped[key] = unscoped.get(key, 0.0) + dt
    causes = idle.idle_by_cause(detail, chip)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]
    names = collections.Counter(n for _, n, _, _ in detail.runtime)
    return {
        "calls": calls, "window_s": reduced.window_s,
        "busy_s": reduced.chip_busy_s(chip),
        "device_idle_pct": (1 - reduced.busy_s / reduced.window_s) * 100,
        "stages_ms": _per_call(by_tf, calls),
        "stages_ms_hlo": _per_call(by_hlo, calls),
        "hlo_agreement": agree / total if total else None,
        "glue_ms": _per_call(glue, calls),
        "glue_unscoped_ms": _per_call(dict(top), calls),
        "idle_ms": _per_call(causes, calls),
        "idle_sum_s": sum(causes.values()),
        "idle_gaps": [[c, s] for c, s in idle.named_gaps(detail, chip)[:10]],
        "clock_margins": idle.clock_margins(detail, chip),
        "runtime_counts": {"programs": len(detail.modules[chip]),
                           "launches": len(detail.launches),
                           "completions": len(detail.completions)},
        "runtime_names": dict(names.most_common(30)),
        "device_ops": reduced.breakdown()["device_ops"],
    }, detail


def write_fixture(path: str, detail, programs, source: str) -> None:
    """``FIXTURE_CALLS`` calls of the window (from the fifth, where the
    window has enough), times in ns from 1 ms before the first of
    them."""
    from benchmarks.chip import idle, scopes

    calls = [s for s in detail.spans if s[0] == "call"]
    waits = [s for s in detail.spans if s[0] == "wait"]
    k = max(0, min(4, len(calls) - FIXTURE_CALLS))
    t0 = calls[k][1] - 1e6
    t1 = waits[k + FIXTURE_CALLS - 1][2]

    def inside(s, e):
        return e > t0 and s < t1

    ops = {str(c): [[n, o, s - t0, e - t0, op] for n, o, s, e, op in evs
                    if inside(s, e)]
           for c, evs in detail.ops.items()}
    kept = {c: [r for r in idle.executions(detail, c)
                if inside(r[1], r[2])] for c in detail.ops}
    # the launch and completion of each kept program, and no other, so
    # that the fixture pairs them as the trace does
    paired = [(idle.LAUNCH, r[5]) for r in kept[min(kept)] if r[5]]
    paired += [(idle.DONE, r[6]) for r in kept[min(kept)] if r[6]]
    runtime = [[th, n, s - t0, e - t0] for th, n, s, e in detail.runtime
               if inside(s, e) and n not in (idle.LAUNCH, idle.DONE)]
    runtime += [["", n, s - t0, e - t0] for n, (s, e) in paired]
    ran = {id(p): p for evs in ops.values()
           for p in scopes.attribute([e[0] for e in evs], programs)
           if p is not None}.values()
    data = {
        "source": source,
        "ops": ops,
        "modules": {str(c): [[n, s - t0, e - t0] for n, s, e, *_ in runs]
                    for c, runs in kept.items()},
        "spans": [[n, s - t0, e - t0] for n, s, e in detail.spans
                  if inside(s, e)],
        "runtime": sorted(runtime, key=lambda x: x[2]),
        "programs": [{"name": p.name, "order": sorted(p.order,
                                                      key=p.order.get),
                      "op_names": [p.op_names[i] for i in sorted(
                          p.order, key=p.order.get)],
                      "args": sorted(p.args)} for p in ran],
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", help="write a trimmed fixture here")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    if "TPU_LOG_DIR" not in os.environ:
        os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, ".chipbench",
                                                 "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    from benchmarks.chip import scopes
    from benchmarks.chip.harness import (WORK_DIR, Bench, NoChip,
                                         enable_compile_cache)
    enable_compile_cache()
    bench = Bench()
    trace_dir = os.path.join(WORK_DIR, "detail", args.workload)
    try:
        w = traced_window(bench, args.workload, args.seed, args.seconds,
                          trace_dir)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    programs = scopes.live_programs()
    chips = [d.id for d in w["devices"]]
    report, detail = read_window(bench, trace_dir, chips, programs)
    traces = max(w["warm_counts"]["matmul_traces"], 1)
    report["program_counters"] = {
        "per_trace": {k: w["warm_counts"][k] / traces
                      for k in ("plans", "gemm_pairs", "gemm_launches")},
        "plan": [dict(zip(("m", "n", "k"), p),
                      **dataclasses.asdict(w["route"].plan(*p)))
                 for p in w["products"]],
        "in_window": w["window_counts"],
    }
    report["device"] = {"kind": w["devices"][0].device_kind,
                        "count": len(chips)}
    if args.fixture:
        write_fixture(args.fixture, detail, programs,
                      f"{report['device']['kind']}, {args.workload}, "
                      f"benchmarks/chip/detail.py: {FIXTURE_CALLS} calls "
                      f"of the traced window, times in ns from 1 ms before "
                      f"the first call")
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
