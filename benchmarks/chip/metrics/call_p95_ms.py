"""call_p95_ms: the 95th percentile of the times of all calls in the
window, each from its dispatch to the end of ``block_until_ready``
(host clock), in ms."""
import numpy as np


def read(run):
    return float(np.percentile(run.call_times, 95)) * 1e3
