#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers on
many seeds, and the control's on a few, in one process.

    python3 benchmarks/chip/control.py --workload dgemm.square16k \\
        --seeds 11,12,13 --control-seeds 11,12,13

Each run is a window of as many calls as the cell checks, at the cell's
own sizes and load, checked as a benchmark run is. The control
(``entries/control_f32.py``) is the plain product in float32 put in the
program's place; it has to come out not correct. One JSON line per run
on standard output. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the TPU runtime's logs stay in the checkout, not in /tmp
    if "TPU_LOG_DIR" not in os.environ:
        os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, ".chipbench",
                                                 "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    from benchmarks.chip.harness import Bench, enable_compile_cache, run_cell
    enable_compile_cache()
    bench = Bench()
    runs = ([(s, None) for s in args.seeds]
            + [(s, "control_f32") for s in args.control_seeds])
    for seed, entry in runs:
        t = time.perf_counter()
        r = run_cell(bench, args.workload, seed, args.seconds, entry=entry)
        line = {"workload": args.workload, "seed": seed,
                "entry": entry or "program", "correct": r["correct"],
                "attempted": r["attempted"],
                "checks": {k: v["value"] for k, v in r["checks"].items()},
                "wall_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
