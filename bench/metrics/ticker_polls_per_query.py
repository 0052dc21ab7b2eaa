"""Service (serve/anns_service): the ticker thread's polls of a batch's
ticket per query, the mean over answers of
``SearchResponse.ticker_polls / batch_size``.  Each poll is a wake-up that
asks for the interpreter lock."""

import numpy as np


def read(run):
    vals = [getattr(a, "ticker_polls", None) for a in run.answers]
    if not vals or None in vals:
        return None
    return float(np.mean([p / a.batch_size
                          for p, a in zip(vals, run.answers)]))
