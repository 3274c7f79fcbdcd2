"""split_ms: device time per call of the split kernel
(``kernels/ozaki_split.py``), in ms, from the device trace. A TPU v5e
trace names its custom calls ``fused_split_dw.<n>``, one per operand."""

KERNELS = ("fused_split_dw",)


def match(name: str, opcode: str) -> bool:
    return name.startswith(KERNELS)


def read(run):
    if run.trace is None:
        return None
    t = run.trace.seconds(match)
    return t / len(run.calls) * 1e3 if t > 0 else None
