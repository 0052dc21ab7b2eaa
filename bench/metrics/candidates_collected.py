"""Executor host stages (core/executor, core/segments): mean candidates
per answered query that its own posting lists gave after dedup and
tombstones, before any predicate and before the scan window's union,
``QueryStats.candidates_collected``, an exact count."""

import numpy as np


def read(run):
    vals = [getattr(a.stats, "candidates_collected", None)
            for a in run.answers]
    if not vals or None in vals:
        return None
    return float(np.mean(vals))
