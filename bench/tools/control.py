"""Reads the control: the reference, put in the program's place and
computed in bfloat16, answering as many queries of a cell's pool as a
run answers, judged by the same comparison as a run.

    python3 bench/tools/control.py --config sift250k --seeds 1 2 3 --queries 2048

Prints, per seed, each compared number beside its limit.
"""

import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=2048)
    args = ap.parse_args()
    import spec
    cfg = spec.config(spec.load(), args.config)
    harness.use_compile_cache()
    harness.find_program()
    harness.devices(1, require_chip=True)
    import check
    import corpus
    import reference
    g = cfg["guarantees"]
    limits = {"unanswered": 0, "malformed": 0, "dist_gap": g["dist_gap_limit"],
              "recall_miss": 1.0 - g["recall_at_10_floor"]}
    for seed in args.seeds:
        t0 = time.perf_counter()
        data, pool = corpus.make(cfg["corpus"], cfg["n_vectors"], cfg["dim"],
                                 harness.POOL, seed)
        q = pool[:args.queries]
        exact, _ = reference.topk(data, q, cfg["top_k"])
        low_ids, low_d = reference.topk(data, q, cfg["top_k"],
                                        precision="bfloat16")
        checks = check.compare(data, q, list(zip(low_ids, low_d)), exact,
                               cfg["top_k"], limits)
        nums = {k: v["value"] for k, v in checks.items()}
        print(f"control {args.config} seed {seed}: bfloat16 reference "
              f"{nums}; fails: {not check.passed(checks)} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
