"""scale_out_ms: device time per call of the operations the program
issued under its ``ozaki.scale_out`` scope, in ms, from the device
trace: the final power-of-two scaling (``ldexp`` by ``e_base``) of both
df32 output planes (``core/executors.py``). Reads nothing where the
program carries no stage scopes (``scopes.py``).

It claims no operations (no ``match``): its time stays in ``glue_ms``,
of which it is a part."""
from benchmarks.chip.scopes import stage_ms

SCOPE = "ozaki.scale_out"


def read(run):
    return stage_ms(run, SCOPE)
