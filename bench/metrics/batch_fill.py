"""Service layer: mean batch size an answered request was served in
(``SearchResponse.batch_size``), over the service's ``max_batch``."""

import numpy as np


def read(run):
    if not run.answers:
        return None
    return float(np.mean([a.batch_size for a in run.answers])
                 / run.serving["max_batch"])
