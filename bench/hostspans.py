"""Puts the device's idle time in the traced window down to the program's
host stages.

The program wraps each scan window's host stages in profiler spans
(``STAGES``), on whichever host thread runs them.  This module reads the
run's trace once, takes the ``bench.window`` span and device 0's idle
intervals in it (the complement of the union of its ops, as ``devtrace``
takes them), and gives for each stage the share of that idle time during
which some host thread was inside one of the stage's spans, and the share
during which no thread was inside any stage span.  Threads run at once,
so the shares can overlap and need not sum to 100.

Span names are compared bare (``executor.rerank``, whatever stats ride
on it); the spans of a stage are merged across every host line.  A trace
with no stage span, as from a program without them, gives nothing.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from devtrace import (DEVICE_PREFIX, OPS_LINE, WINDOW_SPAN, Interval,
                      complement, latest_xplane, union)

STAGES = ("executor.collect", "executor.lut", "executor.scan",
          "executor.scan_wait", "executor.rerank")
UNSTAGED = "unstaged"

_parsed: Dict[str, object] = {}     # the last trace read, by path and mtime


def bare(name: str) -> str:
    """``name#k=v,...#`` (a span's stats folded into its name) -> ``name``."""
    return name.split("#", 1)[0]


def overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two sorted, disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_shares(profile) -> Optional[Dict[str, float]]:
    """Per stage span name, and ``UNSTAGED``: percent of device 0's idle
    time in the window.  ``profile`` is a ``jax.profiler.ProfileData``;
    None where it holds no stage span or the device never idled."""
    spans: Dict[str, List[Interval]] = {s: [] for s in STAGES}
    ops: Dict[str, List[Interval]] = {}
    window: Optional[Interval] = None
    for plane in profile.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if is_dev:
                    ops.setdefault(plane.name, []).append((s, e))
                    continue
                name = bare(ev.name)
                if name == WINDOW_SPAN and window is None:
                    window = (s, e)
                elif name in spans:
                    spans[name].append((s, e))
    if window is None or not any(spans.values()):
        return None
    lo, hi = window
    busy = union(next(iter(ops.values())), lo, hi) if ops else []
    idle = complement(busy, lo, hi)
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0:
        return None
    out = {name: 100.0 * overlap_ns(idle, union(iv, lo, hi)) / idle_ns
           for name, iv in spans.items()}
    staged = union([iv for ivs in spans.values() for iv in ivs], lo, hi)
    out[UNSTAGED] = 100.0 * (1.0 - overlap_ns(idle, staged) / idle_ns)
    return out


def latest_shares() -> Optional[Dict[str, float]]:
    """``idle_shares`` of the newest trace under the harness's trace
    directory (the run's own), read once however many readers ask."""
    import harness
    try:
        path = latest_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    key = f"{path}@{os.path.getmtime(path)}"
    if _parsed.get("key") != key:
        from jax.profiler import ProfileData
        _parsed.clear()
        _parsed.update(key=key,
                       shares=idle_shares(ProfileData.from_file(path)))
    return _parsed["shares"]


def read_share(run, name: str) -> Optional[float]:
    if run.trace is None:
        return None
    shares = latest_shares()
    return None if shares is None else shares[name]
