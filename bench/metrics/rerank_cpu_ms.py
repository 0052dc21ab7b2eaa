"""Executor host stages (core/executor, core/rerank): mean thread CPU time
of the re-rank per answered query, ``QueryStats.cpu_rerank``: the
interval ``rerank_wall_ms`` times, less the waits for the interpreter lock
and the OS."""

import numpy as np


def read(run):
    vals = [getattr(a.stats, "cpu_rerank", None) for a in run.answers]
    if not vals or None in vals:
        return None
    return 1e3 * float(np.mean(vals))
