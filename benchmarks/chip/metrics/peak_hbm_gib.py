"""peak_hbm_gib: the device memory the cell's products need at their
peak, in GiB: ``memory_stats()["peak_bytes_in_use"]`` read after the
window (operands, results and the samples held for the check: the
traffic's ``check.calls`` of them between calls and one more while a
call runs, however many calls the window runs), the largest over the
cell's chips, plus the largest temporary space of the timed programs
from their compiled memory analysis (the slice stacks), which
``peak_bytes_in_use`` leaves out on a TPU v5e. For a DGEMM user it
decides the largest product that fits."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30
