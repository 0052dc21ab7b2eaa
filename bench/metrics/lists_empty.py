"""Executor host stages (core/executor, core/segments): mean posting lists
per answered query that stage ①'s graph search returned and that held no
live row, ``QueryStats.lists_empty``, an exact count.  A build leaves no
list empty, so above 0 it shows lists emptied since (tombstones) or a
build that left some."""

import numpy as np


def read(run):
    vals = [getattr(a.stats, "lists_empty", None) for a in run.answers]
    if not vals or None in vals:
        return None
    return float(np.mean(vals))
