"""glue_ms: device time per call of every operation that no other
per-layer reader in ``BENCHMARK.json`` claims: exponents, DW words,
ldexp, padding and copies (``core/executors.py``, ``core/ozaki.py``),
in ms, from the device trace. A reader claims operations by exporting
``match(name, opcode)``; a new stage metric leaves glue by its own file."""


def read(run):
    if run.trace is None:
        return None
    others = list(run.stages.values())
    t = run.trace.seconds(lambda n, o: not any(f(n, o) for f in others))
    return t / len(run.calls) * 1e3 if t > 0 else None
