"""exponents_ms: device time per call of the operations the program
issued under its ``ozaki.exponents`` scope, in ms, from the device
trace: the operands' per-row exponents (``splitting.row_exponents``) and
their outer sum ``e_base`` (``core/ozaki.py``). Reads nothing where the
program carries no stage scopes (``scopes.py``).

It claims no operations (no ``match``): its time stays in ``glue_ms``,
of which it is a part."""
from benchmarks.chip.scopes import stage_ms

SCOPE = "ozaki.exponents"


def read(run):
    return stage_ms(run, SCOPE)
