"""collective_ms: device time per call of the collectives on the first
chip, in ms, from the device trace (``parallel/ozaki_shard.py``,
``parallel/collectives.py``). They are told by their opcode: XLA names
the int32 psum's all-reduce ``psum.<n>``."""

OPCODES = ("all-reduce", "reduce-scatter", "all-gather",
           "collective-permute", "all-to-all")


def match(name: str, opcode: str) -> bool:
    return opcode.startswith(OPCODES)


def read(run):
    if run.trace is None:
        return None
    t = run.trace.seconds(match, chip=min(run.trace.ops))
    return t / len(run.calls) * 1e3 if t > 0 else None
