"""The open-loop generator's due times."""

import numpy as np
import pytest

from arrivals import due_times


def test_every_seed_offers_the_same_gaps_in_another_order():
    mix = {"loop": "open", "rate_qps": 40}
    a, b = due_times(mix, 30.0, 1), due_times(mix, 30.0, 2**33 + 1)
    assert len(a) == len(b) == 1200
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    assert np.array_equal(a, due_times(mix, 30.0, 1))


def test_due_times_are_ordered_inside_the_span_at_the_rate():
    d = due_times({"loop": "open", "rate_qps": 25}, 40.0, 7)
    assert np.all(np.diff(d) > 0)
    assert 0 < d[0] and d[-1] < 40.0
    # the last arrival is half a mean gap before the end
    assert d[-1] == pytest.approx(40.0 - 0.5 / 25, abs=1e-3)
    gaps = np.diff(d, prepend=0)
    assert np.mean(gaps) == pytest.approx((40.0 - 0.02) / 1000, rel=1e-6)
    # exponential in shape: the median gap is ln 2 of the mean
    assert np.median(gaps) / np.mean(gaps) == pytest.approx(np.log(2),
                                                            rel=0.02)

