"""Prints the shape of a profiler trace: planes, lines, event counts and
the most frequent and longest event names of each line.

    python3 bench/tools/trace_dump.py bench/.traces/sift250k.sat
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import devtrace  # noqa: E402


def main() -> int:
    from jax.profiler import ProfileData
    path = devtrace.latest_xplane(sys.argv[1])
    print(f"{path}: {os.path.getsize(path)} bytes")
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            count = collections.Counter()
            total = collections.Counter()
            first = None
            for ev in line.events:
                count[ev.name] += 1
                total[ev.name] += ev.duration_ns
                first = first if first is not None else ev.start_ns
            print(f"  LINE {line.name!r}: {sum(count.values())} events, "
                  f"first at {first}")
            for name, ns in total.most_common(12):
                print(f"    {ns / 1e6:12.3f} ms x{count[name]:6d}  "
                      f"{name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
