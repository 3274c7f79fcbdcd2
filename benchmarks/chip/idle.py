"""A traced window read in detail: each device operation's stage, and
each idle gap put down to what the runtime and the host were doing.

Beside the ``.xplane.pb`` that ``trace.reduce_trace`` reads, the TPU
profiler writes ``<host>.trace.json.gz``, Chrome trace events on the same
clock (``ts`` and ``dur`` in microseconds). Its device process
``/device:TPU:<n>`` has the threads ``XLA Modules`` (one event per
program execution) and ``XLA Ops`` (one per operation; ``args.tf_op``
is the instruction's ``op_name``, from which ``scopes.stage_of`` takes
its stage). Its host process ``/host:CPU`` holds, on the caller's
thread, the harness's spans (``call``, ``check``, ``wait``), the
program's (``repro.matmul``, ``repro.plan``) and JAX's compiles
(``backend_compile*``); on the runtime's threads each program's launch
(``PJRT_LoadedExecutable_Execute``) and its completion
(``tpu::System::Execute=>Done``, after the runtime has read the device's
sync flag, ``ReadSyncFlag``: one read may complete two programs).

A chip's idle time in the window splits into pieces:

* ``in_call``: a gap that lies within one program's execution;
* ``completion``: from the device's last operation of a program until
  the runtime has completed it (``Execute=>Done``);
* ``dispatch``: from then until the next program's first operation (the
  caller's loop, JAX's dispatch, the launch); the whole gap where the
  next program was launched before the gap began, and at the window's
  start, from the first ``call`` to the first operation;
* ``trace`` / ``compile``: the parts of a gap in which a program span or
  a compile was open on the host.

The profiler aligns host and device clocks only to within about a
millisecond, and a trace can show a program's first operation before
its launch. Host times are moved onto the device's clock by the least
shift that puts every launch before its program's first operation
(``clock_offset``); the margins before that shift are reported too.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

from .trace import DEVICE_PLANE, OPS_LINE, SPANS, parse_op, window_of

MODULES_LINE = "XLA Modules"
PROGRAM_SPANS = ("repro.matmul", "repro.plan")
LAUNCH = "PJRT_LoadedExecutable_Execute"
DONE = "tpu::System::Execute=>Done"
COMPILE = re.compile(r"(^|\W)backend_compile")
CAUSES = ("completion", "dispatch", "in_call", "trace", "compile")


@dataclasses.dataclass
class Detail:
    """A trace's events, in nanoseconds on the trace's clock."""

    ops: dict             # chip -> [(name, opcode, start, end, op_name)]
    modules: dict         # chip -> [(program, start, end)]
    spans: list           # [(name, start, end)]: harness, program, compile
    launches: list        # [(start, end)] of each program's launch
    completions: list     # [(start, end)] of each program's completion
    runtime: list = dataclasses.field(default_factory=list)
    # [(thread, name, start, end)]: every other host event but the
    # Python tracer's

    def reduced(self):
        """``trace.Reduced`` of the window, as the harness reduces it."""
        return window_of({c: [e[:4] for e in evs]
                          for c, evs in self.ops.items()},
                         [s for s in self.spans if s[0] in SPANS])

    def window(self) -> tuple:
        calls = [s for n, s, _ in self.spans if n == "call"]
        waits = [e for n, _, e in self.spans if n == "wait"]
        return min(calls), max(waits)


def read_trace_json(path: str, chips) -> Detail:
    """A ``Detail`` of the listed chips from one ``.trace.json.gz``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    want = {int(c) for c in chips}
    ops = {c: [] for c in want}
    modules = {c: [] for c in want}
    spans, runtime = [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e.get("pid"), "")
        start = round(e["ts"] * 1e3)
        end = start + round(e.get("dur", 0) * 1e3)
        name = e.get("name", "")
        m = DEVICE_PLANE.match(proc)
        if m and int(m.group(1)) in want:
            chip = int(m.group(1))
            line = threads.get((e["pid"], e["tid"]))
            args = e.get("args", {})
            if line == OPS_LINE:
                _, opcode = parse_op(args.get("long_name", name))
                tf_op = args.get("tf_op")
                ops[chip].append((name, opcode, start, end,
                                  tf_op.rstrip(":") if tf_op else None))
            elif line == MODULES_LINE:
                modules[chip].append((name, start, end))
        elif proc.startswith("/host:"):
            if name in SPANS or name in PROGRAM_SPANS or COMPILE.search(name):
                spans.append((name, start, end))
            elif not name.startswith("$"):
                runtime.append((threads.get((e["pid"], e["tid"]), ""), name,
                                start, end))
    missing = [c for c in want if not ops[c]]
    if missing:
        raise ValueError(f"the trace holds no operations of chips {missing}")
    runtime.sort(key=lambda x: x[2])
    launches, completions = runtime_events(runtime)
    return Detail(
        ops={c: sorted(v, key=lambda x: x[2]) for c, v in ops.items()},
        modules={c: sorted(v, key=lambda x: x[1]) for c, v in modules.items()},
        spans=sorted(spans, key=lambda x: x[1]),
        launches=launches, completions=completions, runtime=runtime)


def runtime_events(runtime: list) -> tuple:
    """``(launches, completions)``: the ``(start, end)`` of each
    program's launch and completion among the runtime's host events,
    sorted by start."""
    return ([(s, e) for _, n, s, e in runtime if n == LAUNCH],
            [(s, e) for _, n, s, e in runtime if n == DONE])


def find_trace_json(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .trace.json.gz under {trace_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def executions(detail: Detail, chip) -> list:
    """``(program, start, end, first op start, last op end, launch,
    completion)`` of each program run in the window on ``chip``, in
    order. ``launch`` and ``completion`` are the runtime's host events
    paired with it by order over the whole trace: each program is
    launched once and completes once (None where the counts differ)."""
    t0, t1 = detail.window()
    runs = detail.modules[chip]
    launches, completions = detail.launches, detail.completions
    if len(launches) != len(runs):
        launches = [None] * len(runs)
    if len(completions) != len(runs):
        completions = [None] * len(runs)
    out = []
    ops = detail.ops[chip]
    for (name, s, e), la, co in zip(runs, launches, completions):
        if e <= t0 or s >= t1:
            continue
        inside = [(a, b) for _, _, a, b, _ in ops if s <= a and b <= e]
        first = min((a for a, _ in inside), default=s)
        last = max((b for _, b in inside), default=e)
        out.append((name, s, e, first, last, la, co))
    return out


def _subtract(piece: tuple, covers: list) -> list:
    """Split ``(cause, start, end)`` where the ``(cause, start, end)``
    covers overlap it; the first cover listed wins an overlap."""
    out = [piece]
    for cause, cs, ce in covers:
        nxt = []
        for c, s, e in out:
            if c in ("trace", "compile") or ce <= s or cs >= e:
                nxt.append((c, s, e))
                continue
            if cs > s:
                nxt.append((c, s, cs))
            nxt.append((cause, max(s, cs), min(e, ce)))
            if ce < e:
                nxt.append((c, ce, e))
        out = nxt
    return out


def clock_offset(detail: Detail, chip) -> int:
    """Nanoseconds to take from host times to put them on the device's
    clock: the least shift under which every paired launch precedes its
    program's first operation (0 where none needs one)."""
    return max([0] + [r[5][0] - r[3] for r in executions(detail, chip)
                      if r[5] is not None])


def idle_pieces(detail: Detail, chip) -> list:
    """``(cause, start, end)`` pieces that tile the chip's idle time in
    the window (the window less the union of its operations)."""
    t0, t1 = detail.window()
    runs = executions(detail, chip)
    shift = clock_offset(detail, chip)
    covers = [("compile", s - shift, e - shift) for n, s, e in detail.spans
              if COMPILE.search(n)]
    covers += [("trace", s - shift, e - shift) for n, s, e in detail.spans
               if n in PROGRAM_SPANS]
    pieces = []

    def gap(g0, g1):
        if g1 <= g0:
            return
        if any(s <= g0 and g1 <= e for _, s, e, *_ in runs):
            parts = [("in_call", g0, g1)]
        else:
            done = [r for r in runs if r[4] <= g0]
            nxt = next((r[5] for r in runs if r[3] >= g1), None)
            queued = nxt is not None and nxt[0] - shift <= g0
            if not done or queued:
                parts = [("dispatch", g0, g1)]
            else:
                co = done[-1][6]
                cut = g0 if co is None else min(max(co[1] - shift, g0),
                                                g1)
                parts = [("completion", g0, cut), ("dispatch", cut, g1)]
        for p in parts:
            if p[2] > p[1]:
                pieces.extend(_subtract(p, covers))

    t = t0
    for _, _, s, e, _ in detail.ops[chip]:
        if e <= t0 or s >= t1:
            continue
        gap(t, min(s, t1))
        t = max(t, min(e, t1))
    gap(t, t1)
    return pieces


def idle_by_cause(detail: Detail, chip) -> dict:
    """Seconds of idle time per cause on ``chip``."""
    out = dict.fromkeys(CAUSES, 0.0)
    for c, s, e in idle_pieces(detail, chip):
        out[c] += (e - s) * 1e-9
    return out


def named_gaps(detail: Detail, chip) -> list:
    """``[(cause, seconds)]`` of each idle gap, named by the cause that
    holds most of it, longest first: what the breakdown's ``idle_gaps``
    would read."""
    gaps, cur = [], None
    for c, s, e in idle_pieces(detail, chip):
        if cur is not None and s == cur[2]:
            cur[3][c] = cur[3].get(c, 0) + e - s
            cur[2] = e
        else:
            cur = [None, s, e, {c: e - s}]
            gaps.append(cur)
    named = [(max(g[3], key=g[3].get), (g[2] - g[1]) * 1e-9) for g in gaps]
    return sorted(named, key=lambda g: -g[1])


def clock_margins(detail: Detail, chip) -> dict:
    """The smallest lead, in seconds, of a program's launch on the host
    before its first device operation, and of its completion after its
    last one, on the trace's own clocks (negative where they disagree),
    and ``clock_offset`` in seconds."""
    runs = [r for r in executions(detail, chip)
            if r[5] is not None and r[6] is not None]
    if not runs:
        return {"launch_s": None, "completion_s": None, "shift_s": 0.0,
                "programs": 0}
    return {"launch_s": min(r[3] - r[5][0] for r in runs) * 1e-9,
            "completion_s": min(r[6][0] - r[4] for r in runs) * 1e-9,
            "shift_s": clock_offset(detail, chip) * 1e-9,
            "programs": len(runs)}
