"""Executor host stages (core/executor, core/navgraph): mean host wall
time of the graph traversal and candidate collection per answered query,
``QueryStats.t_graph``.  Wall time on a thread that shares the
interpreter lock with the other replicas, so waits for the lock count."""

import numpy as np


def read(run):
    if not run.answers:
        return None
    return 1e3 * float(np.mean([a.stats.t_graph for a in run.answers]))
