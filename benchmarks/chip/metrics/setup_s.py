"""setup_s: seconds from the process's start to the window's start:
JAX and chip start-up, making the operands on the device, compiling or
loading every program from the compilation cache, and the warm-up
calls (host clock)."""


def read(run):
    return run.setup_s
