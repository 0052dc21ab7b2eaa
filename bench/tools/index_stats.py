"""Describes a configuration's built index and the candidates its queries
collect, to find why some queries collect none.

    python3 bench/tools/index_stats.py --config sift250k --seed 7 \
        --queries 4000 [--n 250000]

Builds the index as a run does (``FusionANNSIndex.build`` on the
configuration's corpus; ``--n`` cuts the corpus for a smaller build), then
prints the posting lists' sizes and, over the first ``--queries`` pool
queries of ``--seed``, how many centroids the graph search returns, how
many of their lists hold members, and how many candidates each query
collects.  For each query that collects none it compares the search's
centroids with the exact nearest centroids.
"""

import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spec as spec_mod  # noqa: E402


def quartiles(x):
    import numpy as np
    return [float(v) for v in np.percentile(x, [0, 25, 50, 75, 100])]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=4000)
    ap.add_argument("--n", type=int, default=0,
                    help="build over the first N corpus rows only")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--root", default=spec_mod.CHECKOUT)
    args = ap.parse_args()
    cfg = spec_mod.config(spec_mod.load(args.root), args.config, args.root)
    harness.use_compile_cache()
    harness.find_program()
    harness.devices(1, require_chip=not args.cpu)
    import numpy as np
    import corpus
    from repro.core import navgraph as ng
    from repro.core.engine import FusionANNSIndex

    data, pool = corpus.make(cfg["corpus"], cfg["n_vectors"], cfg["dim"],
                             harness.POOL, args.seed)
    if args.n:
        data = data[:args.n]
    t = time.perf_counter()
    index = FusionANNSIndex.build(data, harness.anns_config(
        dict(cfg, n_vectors=len(data))))
    print(f"{args.config}: N {len(data)}, build {time.perf_counter() - t:.1f}"
          f" s", flush=True)
    view = index.view()
    sizes = np.array([len(m) for m in view.posting.members])
    g = view.graph
    print(f"lists {len(sizes)}: empty {int((sizes == 0).sum())}, sizes "
          f"min/q1/median/q3/max {quartiles(sizes)}, replication "
          f"{view.posting.replication_factor():.3f}; graph {g.neighbors.shape}"
          f", {0 if g.super_centroids is None else len(g.super_centroids)} "
          f"super-centroids", flush=True)
    top_m = cfg["top_m"]
    cents = g.points
    cn = np.sum(cents.astype(np.float64) ** 2, axis=1)
    n_ret, n_full, n_cand, n_exact = [], [], [], []
    zero = []
    t = time.perf_counter()
    for qi in range(min(args.queries, len(pool))):
        q = pool[qi]
        cids = ng.search(g, q, top_m)
        cands = view.collect_candidates(q, top_m)[0]
        d = cn - 2.0 * cents @ q.astype(np.float64) + float(q @ q)
        exact = np.argsort(d)[:top_m]
        n_ret.append(len(cids))
        n_full.append(int((sizes[cids] > 0).sum()))
        n_cand.append(len(cands))
        n_exact.append(len(np.unique(np.concatenate(
            [view.posting.members[c] for c in exact]))))
        if not len(cands):
            zero.append(qi)
            print(f"query {qi} collects none: search returned {len(cids)} "
                  f"centroids, lists sized {sizes[cids][:16].tolist()}..., "
                  f"distances {np.sort(d[cids])[:4].tolist()}; exact nearest "
                  f"lists sized {sizes[exact][:16].tolist()}..., distances "
                  f"{d[exact][:4].tolist()}; overlap "
                  f"{len(np.intersect1d(cids, exact))}; seeds "
                  f"{g.seed_beam(q).tolist()}", flush=True)
    print(f"{len(n_cand)} queries in {time.perf_counter() - t:.1f} s: "
          f"centroids returned {quartiles(n_ret)}, lists with members "
          f"{quartiles(n_full)}, candidates {quartiles(n_cand)}, candidates "
          f"of the exact nearest centroids {quartiles(n_exact)}; none "
          f"collected by queries {zero}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
