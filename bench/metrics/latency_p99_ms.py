"""Service layer, seen from the client: the 99th percentile of every
window request's latency, as the end-to-end ``latency_p99_ms`` takes it.
In a saturated closed loop the tail swings with every stall of the host
(PERF.md), so there it is a per-layer reading, not a bound."""

import numpy as np


def read(run):
    if not len(run.latencies_s):
        return None
    return 1e3 * float(np.percentile(run.latencies_s, 99))
