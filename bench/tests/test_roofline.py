"""The scan's useful bytes and the peaks table."""

import pytest

import roofline


def test_scan_bytes_counts_the_rows_each_query_scores_and_its_table():
    # two windows: 3 queries that each score a union of 300 rows, and
    # 1 query over 50 rows; pq_m 32, 256 centroids
    rows = [300, 300, 300, 50]
    lut = 32 * 256 * 4
    assert roofline.scan_bytes(rows, 32, 8) == (3 * 300 + 50) * 32 + 4 * lut


def test_known_device_has_its_published_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops"] == 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice, match="TPU v9"):
        roofline.peaks("TPU v9")
