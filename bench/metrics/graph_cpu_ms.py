"""Executor host stages (core/executor, core/navgraph): mean thread CPU
time of the graph traversal and candidate collection per answered query,
``QueryStats.cpu_graph``: the interval ``graph_wall_ms`` times, less the
waits for the interpreter lock and the OS."""

import numpy as np


def read(run):
    vals = [getattr(a.stats, "cpu_graph", None) for a in run.answers]
    if not vals or None in vals:
        return None
    return 1e3 * float(np.mean(vals))
