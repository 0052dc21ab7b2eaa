"""The chip's peaks, and the bytes the device scan needs to read.

The scan's useful bytes are counted from the rows it scores, not from how
they reach the device: every query scores the PQ codes of its scan
window's candidate union (``QueryStats.candidates_scanned``, the union's
row count, reported on each query of the window), ``pq_m`` bytes a row,
through its own float32 lookup table of ``pq_m`` x 2**``pq_nbits``
entries.  The union and its window are the executor's (stage 3 and
``scan_window``): a change to either changes this count, whatever scan
runs underneath.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Sequence

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
LUT_ENTRY_BYTES = 4


class UnknownDevice(KeyError):
    """A device kind the peaks table does not list."""


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; bench/peaks.json "
            f"lists {sorted(table)}")
    return table[device_kind]


def scan_bytes(rows_scanned: Sequence[int], pq_m: int, pq_nbits: int) -> int:
    """Useful HBM bytes of the scans that answered these queries, one
    ``candidates_scanned`` reading per query."""
    lut = pq_m * (1 << pq_nbits) * LUT_ENTRY_BYTES
    return sum(int(r) for r in rows_scanned) * pq_m + len(rows_scanned) * lut
