"""fp64_tflops: the FP64 product's work, ``2 m n k`` (a batch folded
into m) summed over every call in the window, over the window's seconds
(host clock, first call's start to last call's end), in TFLOP/s.

The work is the FP64 product's whatever the route, so a route that
needs fewer int8 GEMMs shows here."""


def read(run):
    return sum(c.fp64_flops for c in run.calls) / run.window_s / 1e12
