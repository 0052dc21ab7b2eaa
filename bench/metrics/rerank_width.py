"""Executor host stages (core/executor, core/rerank): mean over answered
queries of ``QueryStats.rerank_width``, the number of queries stage ⑦
scored together at each lockstep step of the query's re-rank group
(Σ mini-batches ÷ steps); 1.0 where a query re-ranked alone.  A program
without the counter reports nothing."""

import numpy as np


def read(run):
    vals = [getattr(a.stats, "rerank_width", None) for a in run.answers]
    if not vals or None in vals:
        return None
    return float(np.mean(vals))
