"""Looks for the stage that answers a request with fewer than ``k`` ids.

    python3 bench/tools/empty_answers.py --workload sift250k.sat --seed 7 \
        --seconds 60 --out chiprun_out/empty

Runs the cell's set-up and window as a run does, with the executor's
``_finish_into`` wrapped so that every window in which some query resolves
with fewer than ``k`` ids is kept: its queries, each query's candidate
count, the scan's finite distances per query, the union and the bucket.
After the window it

1. replays each kept window through ``_dispatch`` and ``_finish_into`` on
   one thread, and each of its short queries alone;
2. runs the window's pool queries again through ``executor.run`` in
   windows of the serving stack's ``scan_window``, on one thread, and
   counts the short answers there.

Each kept window is written to ``<out>/window<i>.npz``.
"""

import argparse
import json
import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spec as spec_mod  # noqa: E402


def stages(index, query, plan, cfg) -> None:
    """One query through each stage by hand: its candidates, its table,
    the ADC distances of its candidates on the host and on the device, the
    fused scan, and the exact answer over the raw vectors."""
    import dataclasses

    import numpy as np
    q = np.asarray(query, np.float32)
    view = index.view()
    cands = view.collect_candidates(q, plan.top_m)[0]
    print(f"  stages: |q| {float(np.linalg.norm(q))!r} finite "
          f"{bool(np.all(np.isfinite(q)))}; candidates {len(cands)}",
          flush=True)
    from repro.core import pq
    import jax.numpy as jnp
    lut = np.asarray(pq.adc_lut_batch(
        index.codebook, jnp.asarray(index._lut_query(q)[None])))[0]
    print(f"  stages: table {lut.shape} finite "
          f"{int(np.isfinite(lut).sum())} of {lut.size}, min "
          f"{float(np.nanmin(lut))!r} max {float(np.nanmax(lut))!r}",
          flush=True)
    if len(cands):
        codes = np.asarray(view.codes)[view.row_of[cands]].astype(np.int64)
        host = lut[np.arange(lut.shape[0])[None, :], codes].sum(axis=1)
        print(f"  stages: host ADC over the candidates: finite "
              f"{int(np.isfinite(host).sum())} of {len(host)}, min "
              f"{float(np.nanmin(host))!r}", flush=True)
    for label, p in (("dense", plan),
                     ("fused", dataclasses.replace(plan, fused=True))):
        res = index.executor.run(q[None], p)[0]
        print(f"  stages: executor.run {label}: {len(res.ids)} ids "
              f"{np.asarray(res.ids)[:10].tolist()}", flush=True)
    if len(cands):
        raw = np.asarray(index.ssd.vectors)[view.row_of[cands]]
        d = np.sum((raw.astype(np.float64) - q) ** 2, axis=1)
        best = np.argsort(d)[:cfg["top_k"]]
        print(f"  stages: exact over the candidates: ids "
              f"{np.asarray(cands)[best].tolist()}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=spec_mod.CHECKOUT,
                    help="checkout whose BENCHMARK.json names the cell")
    ap.add_argument("--cpu", action="store_true",
                    help="run without a chip (a tiny test configuration)")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    spec = spec_mod.load(args.root)
    wl = spec_mod.workload(spec, args.workload)
    cfg = spec_mod.config(spec, wl["config"], args.root)
    mix = spec_mod.traffic(wl["traffic"], args.root)
    k = int(mix.get("k") or cfg["top_k"])
    st = harness.prepare(cfg, args.seed, int(wl["chips"]),
                         require_chip=not args.cpu)

    import numpy as np
    import drive
    from repro.core.executor import QueryExecutor
    from repro.serve.stack import make_serving_stack

    kept = []
    kept_lock = threading.Lock()
    orig_finish = QueryExecutor._finish_into

    def finish_into(self, w, futures, deadlines):
        orig_finish(self, w, futures, deadlines)
        short = []
        for qi in range(len(w.queries)):
            f = futures[w.start + qi]
            if f.done() and not f.cancelled() and f.exception() is None \
                    and len(f.result().ids) < k:
                short.append(qi)
        if short:
            vals = np.asarray(w.vals)
            rec = {"queries": np.asarray(w.queries), "short": short,
                   "n_cand": [len(p) for p in w.per_q],
                   "n_finite": np.isfinite(vals).sum(axis=1).tolist(),
                   "union": len(w.union), "vals_shape": list(vals.shape),
                   "top_n": [p.top_n for p in w.plans],
                   "k": [p.k for p in w.plans],
                   "thread": threading.current_thread().name,
                   "per_q": [np.asarray(p) for p in w.per_q],
                   "vals": vals, "pos": np.asarray(w.pos),
                   "plans": list(w.plans)}
            with kept_lock:
                kept.append(rec)

    QueryExecutor._finish_into = finish_into
    router = make_serving_stack(st.index)
    try:
        nxt = harness.warm_up(router, st, mix, args.seed)
        first = nxt
        led = drive.run_phase(router, st.pool, nxt, mix, args.seconds,
                              args.seed)
    finally:
        router.stop()
        QueryExecutor._finish_into = orig_finish
    short_reqs = [i for i, a in enumerate(led.answer)
                  if a is not None and len(a.ids) < k]
    print(f"window: {len(led)} requests from pool index {first}; short "
          f"answers at requests {short_reqs}, pool indices "
          f"{[led.query_idx[i] for i in short_reqs]}", flush=True)
    for i in short_reqs:
        a = led.answer[i]
        print(f"  request {i}: ids {np.asarray(a.ids).tolist()} batch_size "
              f"{a.batch_size} stats {a.stats}", flush=True)
    print(f"windows kept by the wrapped _finish_into: {len(kept)}",
          flush=True)

    ex = st.index.executor
    for wi, rec in enumerate(kept[:10]):
        print(f"kept window {wi} ({rec['thread']}): B "
              f"{len(rec['queries'])} union {rec['union']} vals "
              f"{rec['vals_shape']} short {rec['short']} candidates "
              f"{rec['n_cand']} finite {rec['n_finite']} top_n "
              f"{rec['top_n']} k {rec['k']}", flush=True)
        np.savez(os.path.join(args.out, f"window{wi}.npz"),
                 queries=rec["queries"], vals=rec["vals"], pos=rec["pos"],
                 **{f"per_q{j}": p for j, p in enumerate(rec["per_q"])})
        for label, qs, plans in (
                ("whole window", rec["queries"], rec["plans"]),
                *((f"query {qi} alone", rec["queries"][qi:qi + 1],
                   rec["plans"][qi:qi + 1]) for qi in rec["short"])):
            for rep in range(2):
                w = ex._dispatch(qs, plans)
                vals = np.asarray(w.vals)
                from repro.core.futures import QueryFuture
                futs = [QueryFuture(tag=j) for j in range(len(qs))]
                w.start = 0
                ex._finish_into(w, futs, [None] * len(qs))
                lens = [len(f.result().ids) for f in futs]
                print(f"  replay {label} #{rep}: union {len(w.union)} "
                      f"candidates {[len(p) for p in w.per_q]} finite "
                      f"{np.isfinite(vals).sum(axis=1).tolist()} answer "
                      f"lengths {lens}", flush=True)
        for qi in rec["short"]:
            stages(st.index, rec["queries"][qi], rec["plans"][qi], cfg)

    # the window's pool queries again, on one thread, no service
    plan = st.index.plan(window=st.knobs["scan_window"],
                         inflight_depth=st.knobs["inflight_depth"])
    idx = np.asarray(led.query_idx, np.int64) % len(st.pool)
    t0 = time.perf_counter()
    results = []
    step = 256
    for s in range(0, len(idx), step):
        results += ex.run(st.pool[idx[s:s + step]], plan)
    short_run = [int(idx[j]) for j, r in enumerate(results) if len(r.ids) < k]
    print(f"executor.run over the window's {len(idx)} pool queries, "
          f"{time.perf_counter() - t0:.1f} s: short answers at pool indices "
          f"{short_run}", flush=True)
    with open(os.path.join(args.out, "summary.json"), "w") as fh:
        json.dump({"short_requests": short_reqs,
                   "short_pool": [int(led.query_idx[i]) for i in short_reqs],
                   "short_run_pool": short_run,
                   "kept": [{kk: v for kk, v in r.items()
                             if kk in ("short", "n_cand", "n_finite", "union",
                                       "vals_shape", "top_n", "k", "thread")}
                            for r in kept]}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
