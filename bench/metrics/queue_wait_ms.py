"""Service layer (serve/router, serve/anns_service): mean time an answered
request waited for its batch to form, from ``SearchResponse.t_queue_s``."""

import numpy as np


def read(run):
    if not run.answers:
        return None
    return 1e3 * float(np.mean([a.t_queue_s for a in run.answers]))
