"""Runs cells one after another, each in a process of its own, as the
benchmark's check does, and keeps what each printed.

    python3 bench/tools/runs.py --out bench/.runs/sets \
        --workload sift250k.sat --seeds 11 12 13 --seconds 30 [--trace 1]

Each run's output goes to ``<out>/<workload>.<seed>.t<trace>.{out,err}``
(``.<n>`` before the suffix for the n-th run of a seed); one summary
line per run is printed: the seed, ``correct``, the metrics and the
compared numbers.  Given two or more runs it prints each metric's
quartile spread as a share of its median.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    results = []
    for seed in args.seeds:
        stem = os.path.join(args.out,
                            f"{args.workload}.{seed}.t{args.trace}")
        again = 1
        while os.path.exists(stem + (f".{again}" if again > 1 else "")
                             + ".out"):
            again += 1      # a seed run again keeps the earlier output
        if again > 1:
            stem += f".{again}"
        t0 = time.perf_counter()
        with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
            rc = subprocess.call(
                [sys.executable, RUN, "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds),
                 "--trace", str(args.trace)], stdout=out, stderr=err)
        held = time.perf_counter() - t0
        with open(stem + ".out") as fh:
            lines = fh.read().strip().splitlines()
        try:
            res = json.loads(lines[-1])
        except (IndexError, ValueError):
            print(f"{args.workload} seed {seed}: rc {rc}, no result "
                  f"({held:.1f} s)", flush=True)
            continue
        results.append(res)
        mets = {k: v["value"] for k, v in res["metrics"].items()}
        chk = {k: v["value"] for k, v in res["checks"].items()}
        print(f"{args.workload} seed {seed}: rc {rc} correct "
              f"{res['correct']} attempted {res['attempted']} failed "
              f"{res['failed']} held {held:.1f} s metrics {json.dumps(mets)} "
              f"checks {json.dumps(chk)} device "
              f"{json.dumps(res['device'])}", flush=True)
    if len(results) >= 2:
        names = results[0]["metrics"]
        for n in names:
            vals = [r["metrics"][n]["value"] for r in results
                    if n in r["metrics"]]
            print(f"spread {args.workload} {n}: median "
                  f"{statistics.median(vals)!r} iqr/median {spread(vals)!r} "
                  f"values {vals}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
