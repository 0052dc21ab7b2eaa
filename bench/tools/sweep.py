"""Finds the highest open-loop rate a configuration's serving stack
sustains, in one process on the chip: the saturated closed-loop rate
first, then open-loop Poisson traffic at given shares of it.

    python3 bench/tools/sweep.py --config sift250k --seed 5 \
        --shares 0.5 0.7 0.8 0.9 1.0 1.1 --seconds 20

A rate is sustained when every request is answered, completions keep
pace with arrivals, and the second half of the window waits no longer
than the first (no growing backlog).
"""

import argparse
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--closed", default="closed64")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--shares", type=float, nargs="+",
                    default=[0.25, 0.5, 0.75, 0.9])
    args = ap.parse_args()
    cfg = spec.config(spec.load(), args.config)
    st = harness.prepare(cfg, args.seed, 1)
    import drive
    from repro.serve.stack import make_serving_stack
    router = make_serving_stack(st.index)
    try:
        closed = spec.traffic(args.closed)
        nxt = harness.warm_up(router, st, closed, args.seed)
        led = drive.run_phase(router, st.pool, nxt, closed, args.seconds,
                              args.seed)
        nxt += len(led)
        done = np.asarray(led.done)
        sat = np.sum((done >= led.t_open) & (done < led.t_close)) / (
            led.t_close - led.t_open)
        print(f"closed {args.closed}: {sat:.3f} queries/s", flush=True)
        for share in args.shares:
            rate = share * sat
            mix = {"loop": "open", "rate_qps": rate, "k": closed.get("k")}
            led = drive.run_phase(router, st.pool, nxt, mix, args.seconds,
                                  args.seed + nxt)
            nxt += len(led)
            lat = led.latencies_s() * 1e3
            done = np.asarray(led.done)
            answered = sum(a is not None for a in led.answer)
            in_win = np.sum((done >= led.t_open) & (done < led.t_close))
            half = len(lat) // 2
            first, second = np.median(lat[:half]), np.median(lat[half:])
            late = 1e3 * (np.asarray(led.sent) - np.asarray(led.due))
            ok = (answered == len(led)
                  and in_win >= 0.95 * len(led) and second <= 1.5 * first)
            print(f"open {share:.2f} x sat = {rate:.3f}/s: {len(led)} sent, "
                  f"{answered} answered, {in_win} inside the window; "
                  f"p50 {np.median(lat):.1f} ms, p99 "
                  f"{np.percentile(lat, 99):.1f} ms; median wait first "
                  f"half {first:.1f} ms, second half {second:.1f} ms; "
                  f"generator late max {late.max():.2f} ms; sustained "
                  f"{ok}", flush=True)
    finally:
        router.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
