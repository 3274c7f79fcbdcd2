"""layout_ms: device time per call of the operations the program
issued under its ``ozaki.layout`` scope, in ms, from the device trace:
the front door's transposes of B and its batch fold and unfold
(``api.py``). XLA carries the transposes out as copies of the arguments,
which keep the argument's name; ``scopes.py`` counts those here. Reads
nothing where the program carries no stage scopes.

It claims no operations (no ``match``): its time stays in ``glue_ms``,
of which it is a part."""
from benchmarks.chip.scopes import stage_ms

SCOPE = "ozaki.layout"


def read(run):
    return stage_ms(run, SCOPE)
