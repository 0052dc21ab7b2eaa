"""Executor host stages (core/executor, core/rerank): mean host wall time
of the re-rank against the raw vectors per answered query,
``QueryStats.t_rerank``, waits for the interpreter lock included."""

import numpy as np


def read(run):
    if not run.answers:
        return None
    return 1e3 * float(np.mean([a.stats.t_rerank for a in run.answers]))
