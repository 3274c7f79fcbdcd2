"""device_idle_pct: ``1 - busy / window`` of the traced window, in %,
with busy the union of the intervals in which an operation ran on the
chip, averaged over the cell's chips (device trace)."""


def read(run):
    if run.trace is None:
        return None
    return (1.0 - run.trace.busy_s / run.trace.window_s) * 100.0
