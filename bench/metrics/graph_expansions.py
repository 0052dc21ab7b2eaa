"""Executor host stages (core/executor, core/navgraph): mean graph vertices
stage ①'s search expanded per answered query,
``QueryStats.graph_expansions``, an exact count.  ``graph_cpu_ms`` over
it is the host CPU time per expansion."""

import numpy as np


def read(run):
    vals = [getattr(a.stats, "graph_expansions", None) for a in run.answers]
    if not vals or None in vals:
        return None
    return float(np.mean(vals))
