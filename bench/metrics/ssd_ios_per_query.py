"""SSD tier (core/io_sim): mean page reads per answered query,
``QueryStats.ios``, an exact count.  The tier is simulated in host memory,
so it costs no storage time here."""

import numpy as np


def read(run):
    if not run.answers:
        return None
    return float(np.mean([a.stats.ios for a in run.answers]))
