"""The one traffic generator.  A traffic mix is a data file,
``bench/traffic/<mix>.json``, that this module reads:

``loop``
    ``"closed"``: ``outstanding`` requests are always in flight; each
    answer sends the next request (batch clients that wait for replies).
    ``"open"``: requests are sent when due, whether or not earlier ones
    have been answered (independent users).
``rate_qps`` (open loop)
    The mean arrival rate.
``k``, ``top_n``
    Per-request knobs sent with every request (``top_n`` may be absent:
    the configuration's own then holds).
``warmup_s``
    Seconds of the same traffic sent before the window opens.

Open-loop arrivals are Poisson in shape, with one fixed set of gaps for
every seed: the ``n`` gaps are the midpoint quantiles of an exponential
distribution, in an order the seed draws.  Every seed then offers the same
number of requests with the same gaps, so runs differ in order, not in
load.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def due_times(mix: Dict, seconds: float, seed: int) -> np.ndarray:
    """Send times, in seconds from the start of a span of ``seconds``."""
    rate = float(mix["rate_qps"])
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = np.random.default_rng(seed).permutation(gaps)
    # the n gaps span the offered work, less half a mean gap at the end
    return np.cumsum(gaps) * ((n - 0.5) / (rate * gaps.sum()))
