"""gemm_roofline: the int8 slice-pair GEMM stage's share of its
roofline, in %.

The least time the chip could take for the traced calls' GEMM stage is
the larger of its operations over the int8 peak and its bytes over the
HBM bandwidth (``counts.call_counts``: kept pairs x 2 m n k per chip;
slice stacks read once, the stage's output written once); the share is
that over the device time of the GEMM operations in the trace, averaged
over the chips. Both counts are lower bounds on the real work, so the
share cannot pass 100% unless the time leaves part of the work out.

GEMM operations, as a TPU v5e trace names them: the Pallas GEMM
kernels' custom calls (``int8_matmul_nt_epilogue_dw.<n>``, one per
anti-diagonal group, on one chip) and XLA's int8 dots on the k-shard
path, which lower to ``convolution`` instructions, each fused with the
add of its anti-diagonal into an output fusion (``fusion(kOutput)``:
all 45 of them, and only they, in the k-shard program)."""
from benchmarks.chip.counts import roofline_seconds

KERNELS = ("int8_matmul_nt",)
OPCODES = ("convolution", "fusion(kOutput)")


def match(name: str, opcode: str) -> bool:
    return name.startswith(KERNELS) or opcode in OPCODES


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    busy = run.trace.seconds(match)
    if busy <= 0:
        return None
    ideal = sum(roofline_seconds(c.gemm_ops, c.gemm_bytes, run.peaks)[0]
                for c in run.calls)
    return ideal / busy * 100.0
