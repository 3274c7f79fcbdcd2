"""Each device operation's stage, as the program's own scopes name it.

The program wraps the work of each stage in a ``jax.named_scope``
(``repro.tracing``: ``ozaki.layout``, ``ozaki.exponents``,
``ozaki.split``, ``ozaki.gemm``, ``ozaki.scale_out``). The scopes end in
each compiled instruction's ``op_name`` metadata, which a TPU trace
carries as the operation's ``tf_op``; an operation's stage is the
innermost ``ozaki.*`` component of that name. Some operations keep no
scope. The accumulator zero-fills (broadcasts of a constant) and XLA's
async copies and slices carry no metadata. The copies by which XLA's
layout assignment carries out the front door's transposes keep the name
of the argument they copy (``bh``): in a program that carries stage
scopes such an argument copy is counted under ``ozaki.layout``.

``reduce_trace``'s events hold each operation's instruction name, not its
metadata, so a metric reader finds the metadata in the compiled programs
the process still holds (``live_programs``). Instruction names are unique
only within one program, and the window holds more than one program (the
timed product and the harness's sample ``take``): ``attribute`` puts each
run of operations down to the program whose schedule it follows.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Iterable, Optional

PREFIX = "ozaki."
LAYOUT = "ozaki.layout"
# entry-computation instructions that run nothing on the device
_NO_OP = frozenset(("parameter", "constant", "bitcast", "get-tuple-element",
                    "tuple"))
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = .*?[\]\)}] "
                    r"([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def stage_of(op_name: Optional[str], args: Iterable[str] = ()) -> Optional[str]:
    """The innermost ``ozaki.*`` component of an ``op_name`` (a trace's
    ``tf_op`` may end in ``:``), ``ozaki.layout`` for a copy of one of
    the program's ``args``, else None."""
    if not op_name:
        return None
    name = op_name.rstrip(":")
    parts = [p for p in name.split("/") if p.startswith(PREFIX)]
    if parts:
        return parts[-1]
    return LAYOUT if name in args else None


@dataclasses.dataclass
class Program:
    """One compiled program: its device instructions in schedule order
    and each one's ``op_name``."""

    name: str
    order: dict          # instruction name -> position in the schedule
    op_names: dict       # instruction name -> op_name (None: no metadata)
    args: frozenset      # op_names of the entry computation's parameters

    @functools.cached_property
    def scoped(self) -> bool:
        return any(stage_of(o) for o in self.op_names.values())

    def stage(self, instr: str) -> Optional[str]:
        if not self.scoped:
            return None
        return stage_of(self.op_names.get(instr), self.args)


def parse_program(text: str) -> Program:
    """A ``Program`` from an HLO module's text (``HloModule.to_string()``
    of a compiled program, which lists the entry computation in its
    schedule's order)."""
    head = text.split("\n", 1)[0]
    name = head.split()[1].rstrip(",") if head.startswith("HloModule") \
        else ""
    entry = text[text.index("\nENTRY"):]
    order, op_names, args = {}, {}, set()
    for line in entry.splitlines()[1:]:
        m = _INSTR.match(line)
        if not m:
            continue
        instr, opcode = m.groups()
        op = _OP_NAME.search(line)
        if opcode == "parameter":
            if op:
                args.add(op.group(1))
        elif opcode not in _NO_OP:
            order[instr] = len(order)
            op_names[instr] = op.group(1) if op else None
    return Program(name, order, op_names, frozenset(args))


def live_programs() -> list:
    """Every program the process holds compiled for its first device."""
    import jax
    client = jax.devices()[0].client
    return [parse_program(m.to_string())
            for ex in client.live_executables() for m in ex.hlo_modules()]


def _run_length(program: Program, names: list, i: int) -> int:
    """How many operations from ``names[i]`` on follow ``program``'s
    schedule: each a new instruction of it, later than the one before."""
    j, pos = i, -1
    while j < len(names):
        p = program.order.get(names[j])
        if p is None or p <= pos:
            break
        pos, j = p, j + 1
    return j - i


def attribute(names: list, programs: list) -> list:
    """For operation names in time order on one chip, the program each
    ran in (None where no program has it). An execution of a program is
    a run of its instructions in its schedule's order; at each run's
    start the program that explains the longest run wins."""
    out = [None] * len(names)
    i = 0
    while i < len(names):
        best, length = None, 0
        for p in programs:
            n = _run_length(p, names, i)
            if n > length:
                best, length = p, n
        if best is None:
            i += 1
            continue
        out[i:i + length] = [best] * length
        i += length
    return out


def op_stages(trace, programs: list) -> Optional[dict]:
    """chip -> the stage of each of ``trace.ops[chip]`` (None: none), or
    None where some operation ran in no program of ``programs``."""
    out = {}
    for chip, evs in trace.ops.items():
        names = [e[0] for e in evs]
        ran = attribute(names, programs)
        if any(p is None for p in ran):
            return None
        out[chip] = [p.stage(n) for n, p in zip(names, ran)]
    return out


def stage_seconds(trace, stages: dict, stage: str) -> float:
    """Device seconds of the operations in ``stage``, averaged over the
    chips."""
    total = sum(e - s for c, evs in trace.ops.items()
                for (_, _, s, e), st in zip(evs, stages[c]) if st == stage)
    return total * 1e-9 / len(trace.ops)


def stage_ms(run, stage: str):
    """A per-layer reader's value: device ms per call of the operations
    the program issued under ``stage``. None without a trace, where no
    program the process holds carries stage scopes, or where an
    operation of the window is put down to none of them (a program no
    longer held): its stage is unknown, and a sum without it would read
    low."""
    if run.trace is None:
        return None
    programs = live_programs()
    if not any(p.scoped for p in programs):
        return None
    stages = op_stages(run.trace, programs)
    if stages is None:
        return None
    return stage_seconds(run.trace, stages, stage) / len(run.calls) * 1e3
