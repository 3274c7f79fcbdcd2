"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: one plane per chip
(``/device:TPU:<n>``) whose ``XLA Ops`` line holds every operation that
ran on the chip, and host planes whose threads hold the harness's own
spans (``call``, ``wait``, ``check``). Device and host events share one
clock, aligned by the profiler to within about a millisecond.

On a TPU v5e an operation's event is named by its whole HLO
instruction, ``%int8_matmul_nt_epilogue_dw.9 = (f32[...], ...)
custom-call(...)``; the reduction keeps the instruction's name
(``int8_matmul_nt_epilogue_dw.9``) and its opcode (``custom-call``;
``fusion(kOutput)`` for a fusion of that kind).

The traced window runs from the first ``call`` span's start to the last
``wait`` span's end. Busy time is the union of the chip's operation
intervals inside the window; idle time is the rest. Idle gaps are named
by the host span that was open when the gap began (``host`` where none
was: the harness's own loop).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("call", "wait", "check")
# the opcode follows the result shape: "...} fusion(", "...) custom-call("
_OPCODE = re.compile(r"[\]\)}] ([a-z][a-z0-9\-]*)\(")
_FUSION_KIND = re.compile(r"\bkind=(k[A-Za-z]+)")


def parse_op(text: str) -> tuple[str, str]:
    """``(name, opcode)`` of an HLO instruction's text; a fusion's opcode
    carries its kind, ``fusion(kOutput)`` (XLA:TPU's fusions rooted at a
    convolution), and a bare name has the opcode ``""``."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    if opcode == "fusion":
        kind = _FUSION_KIND.search(rest)
        if kind:
            opcode = f"fusion({kind.group(1)})"
    return name.strip().lstrip("%"), opcode


@dataclasses.dataclass
class Reduced:
    """A traced window, in nanoseconds on the trace's clock."""

    start: float
    end: float
    ops: dict                    # chip -> [(name, opcode, start, end)]
    spans: list                  # [(name, start, end)] host spans, sorted
    calls: int                   # ``call`` spans in the window

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    def chip_busy_s(self, chip) -> float:
        return _union_ns(self.ops[chip]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(self.chip_busy_s(c) for c in self.ops) / len(self.ops)

    def seconds(self, match: Callable[[str, str], bool], chip=None) -> float:
        """Device seconds of the operations for which ``match(name,
        opcode)`` holds: on one chip, or averaged over the chips."""
        chips = list(self.ops) if chip is None else [chip]
        total = sum(e - s for c in chips for n, o, s, e in self.ops[c]
                    if match(n, o))
        return total * 1e-9 / len(chips)

    def op_seconds(self, chip=None) -> dict:
        """Seconds per operation name, averaged over the chips."""
        chips = list(self.ops) if chip is None else [chip]
        out: dict = {}
        for c in chips:
            for n, _, s, e in self.ops[c]:
                out[n] = out.get(n, 0.0) + (e - s) * 1e-9 / len(chips)
        return out

    def idle_gaps(self, chip=None) -> list:
        """``[(host activity, seconds)]`` of every idle gap on a chip
        (the first by default), longest first."""
        chip = min(self.ops) if chip is None else chip
        gaps = []
        t = self.start
        for _, _, s, e in self.ops[chip]:
            if s > t:
                gaps.append((self.host_activity(t), (s - t) * 1e-9))
            t = max(t, e)
        if self.end > t:
            gaps.append((self.host_activity(t), (self.end - t) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    def host_activity(self, t: float) -> str:
        """The innermost harness span open at time ``t``."""
        name = "host"
        best = None
        for n, s, e in self.spans:
            if s <= t < e and (best is None or e - s < best):
                name, best = n, e - s
        return name

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:10]]}


def _union_ns(events: Iterable) -> float:
    """Length of the union of ``(name, opcode, start, end)`` intervals,
    sorted by start."""
    total, cur_s, cur_e = 0.0, None, None
    for *_, s, e in events:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_of(ops: dict, spans: list) -> Reduced:
    """Clip device operations to the window the host spans mark."""
    calls = [(s, e) for n, s, e in spans if n == "call"]
    waits = [(s, e) for n, s, e in spans if n == "wait"]
    if not calls or not waits:
        raise ValueError("the trace holds no call and wait spans")
    start, end = calls[0][0], waits[-1][1]
    clipped = {}
    for chip, evs in ops.items():
        inside = ((n, o, max(s, start), min(e, end))
                  for n, o, s, e in evs if e > start and s < end)
        clipped[chip] = sorted(inside, key=lambda x: x[2])
    inside = sorted((x for x in spans if x[2] > start and x[1] < end),
                    key=lambda x: x[1])
    return Reduced(start=start, end=end, ops=clipped, spans=inside,
                   calls=len(calls))


def read_xplane(path: str, chips: Iterable[int]):
    """``(ops, spans)`` from one ``.xplane.pb``: the ``XLA Ops`` events of
    the listed chips, as ``(name, opcode, start, end)``, and the
    harness's host spans, as ``(name, start, end)``."""
    import jax
    want = set(chips)
    pd = jax.profiler.ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in want:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((*parse_op(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
            ops[int(m.group(1))] = sorted(evs, key=lambda x: x[2])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS)
    missing = want - set(ops)
    if missing:
        raise ValueError(f"the trace holds no operations of chips "
                         f"{sorted(missing)}")
    return ops, sorted(spans, key=lambda x: x[1])


def reduce_trace(trace_dir: str, devices) -> Reduced:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace file under {trace_dir}, "
                         f"found {len(paths)}")
    ops, spans = read_xplane(paths[0], [d.id for d in devices])
    return window_of(ops, spans)
