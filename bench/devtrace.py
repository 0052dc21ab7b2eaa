"""Reduces a profiler trace of the measured window to device numbers.

The benchmark wraps its window in a host span named ``bench.window``; only
device activity inside that span counts.  A device is a plane whose name
starts with ``/device:``; the time an operation ran is an event on its
``XLA Ops`` line, and the time a compiled program ran is an event on its
``XLA Modules`` line, named after the jitted function.

- busy: the union of the op intervals, averaged over the devices;
- program time: per program name, the summed module durations;
- idle gaps: the stretches of the window in which no op ran, each named by
  the host event that covered most of it, where one covered half of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    program_s: Dict[str, float]
    gaps: List[Tuple[str, float]]       # (what the host was doing, seconds)

    def breakdown(self) -> Dict[str, list]:
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def complement(covered: List[Interval], lo: float,
               hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in covered:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _program_name(name: str) -> str:
    """``jit_foo(1234)`` -> ``jit_foo``."""
    return re.sub(r"\(\d+\)$", "", name)


def summarize(profile) -> Summary:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    host: List[Tuple[str, float, float]] = []
    ops: Dict[str, List[Interval]] = {}
    programs: Dict[str, List[Interval]] = {}
    window: Optional[Interval] = None
    for plane in profile.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if not is_dev:
                    if ev.name == WINDOW_SPAN and window is None:
                        window = (s, e)
                    else:
                        host.append((ev.name, s, e))
                elif line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).append((s, e))
                elif line.name == MODULES_LINE:
                    programs.setdefault(_program_name(ev.name), []).append(
                        (s, e))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    busy = [union(iv, lo, hi) for iv in ops.values()]
    busy_ns = (sum(e - s for iv in busy for s, e in iv) / len(busy)
               if busy else 0.0)
    program_s = {n: sum(e - s for s, e in union(iv, lo, hi)) / 1e9
                 for n, iv in programs.items()}
    gaps = complement(busy[0], lo, hi) if busy else [(lo, hi)]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_activity(host, s, e), (e - s) / 1e9)
             for s, e in gaps[:TOP]]
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9,
                   program_s={n: v for n, v in program_s.items() if v > 0},
                   gaps=named)


NO_EVENT = "no runtime event (host Python)"


def _host_activity(host: List[Tuple[str, float, float]], lo: float,
                   hi: float) -> str:
    best, best_ns = NO_EVENT, 0.5 * (hi - lo)
    for name, s, e in host:
        cover = min(e, hi) - max(s, lo)
        if cover > best_ns:
            best, best_ns = name, cover
    return best
