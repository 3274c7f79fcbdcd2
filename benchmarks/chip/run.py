#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload dgemm.square16k --seed 7 \\
        --seconds 20 --trace 0

Cells, configurations, traffic mixes, limits and metrics are files,
found by the names in ``BENCHMARK.json`` (see ``harness.py``). The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, and last ``checks``, each number compared
with its limit; the same comparisons end standard error. The command
exits nonzero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the TPU runtime's logs stay in the checkout, not in /tmp
    if "TPU_LOG_DIR" not in os.environ:
        os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, ".chipbench",
                                                 "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    from benchmarks.chip.harness import (Bench, NoChip, enable_compile_cache,
                                         run_cell)
    enable_compile_cache()

    try:
        result = run_cell(Bench(), args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
