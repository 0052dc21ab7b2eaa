"""Smoke run of the FusionANNS serving path on a TPU.

    python chip_smoke.py               # one chip: build, serve, check recall
    python chip_smoke.py --chips 4     # four chips: sharded scans + replicas

The deployment is the SIFT shape (``configs/anns_datasets.SIFT1B``: 128-d,
L2, 32-byte PQ codes, top_m=64, top_n=512, top_k=10) at N = 1,000,000, the
size of the public SIFT1M / BIGANN-1M set, generated from ``--seed``.

One chip: ``FusionANNSIndex.build``, then 256 queries through
``make_serving_stack`` (router -> service -> executor) and ``ANNSClient``,
once on the dense scan and once on the fused scan.  Recall@10 against the
exact ground truth must reach 0.9, and the fused top-k ids must equal the
dense ones.

Four chips (``--chips 4``): the same build, then the dense and fused scans
row-sharded over a 2x2 mesh and a 4-replica serving stack over
``split_mesh``, each checked id for id against the one-device executor.

Every line but the last is a report; the last is one JSON object naming the
device.  With no TPU the script exits non-zero before any work.  Stage
times are host-clock seconds from ``QueryStats``, not device times.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

N_SIFT1M = 1_000_000
N_QUERIES = 256


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds spent in XLA compilation (persistent-cache reads included)
    and cache hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits

    def report(self, phase: str, since) -> None:
        s, p, h = self.snapshot()
        print(f"compile [{phase}]: {s - since[0]:.2f} s over {p - since[1]} "
              f"programs, {h - since[2]} persistent-cache hits")


def sift1m_deployment(n: int, n_queries: int, seed: int):
    """The SIFT-shaped corpus and held-out queries (same mixture), float32."""
    import numpy as np

    from repro.configs.anns_datasets import SIFT1B
    from repro.data.synthetic import clustered_vectors

    cfg = dataclasses.replace(SIFT1B, name="sift1m", n_vectors=n,
                              dtype="float32", n_posting_fraction=0.02)
    rng = np.random.default_rng(seed)
    allv = clustered_vectors(rng, n + n_queries, cfg.dim,
                             n_clusters=max(16, n // 400))
    return cfg, allv[:n], allv[n:]


def build(cfg, data, seed: int, clock: CompileClock):
    from repro.core.engine import FusionANNSIndex

    since = clock.snapshot()
    t0 = time.perf_counter()
    index = FusionANNSIndex.build(data, cfg, seed=seed)
    index.codes.block_until_ready()
    print(f"build: {time.perf_counter() - t0:.2f} s host wall "
          f"({index.posting.n_clusters} posting lists, replication "
          f"{index.posting.replication_factor():.3f}x)")
    clock.report("build", since)
    return index


def serve(index, queries, clock: CompileClock, label: str, **stack_kw):
    """Serve every query through the stack; returns (responses, router)."""
    import numpy as np

    from repro.serve.client import ANNSClient, SearchRequest
    from repro.serve.stack import make_serving_stack

    since = clock.snapshot()
    router = make_serving_stack(index, **stack_kw)
    try:
        t0 = time.perf_counter()
        resps = ANNSClient(router).search_many(
            [SearchRequest(query=q, tag=i) for i, q in enumerate(queries)],
            timeout=600)
        wall = time.perf_counter() - t0
    finally:
        router.stop()
    served = router.stats_rollup()["served"]
    check(served == len(queries) == len(resps),
          f"{label}: served {served} of {len(queries)} queries")
    print(f"serve [{label}]: {served} queries in {wall:.2f} s host wall, "
          f"{router.n_replicas} replicas")
    union = [r.stats.candidates_scanned for r in resps]
    print(f"serve [{label}]: scan-window candidate union mean "
          f"{np.mean(union):.1f}, max {max(union)} (dense bucket "
          f"{1 << int(np.ceil(np.log2(max(max(union), 1))))})")
    for f in ("t_graph", "t_rerank"):
        mean = np.mean([getattr(r.stats, f) for r in resps])
        print(f"serve [{label}]: mean {f} {mean:.6f} s (host clock, "
              "per query)")
    clock.report(f"serve {label}", since)
    return resps, router


def agree(name: str, ref, ids, ref_name: str) -> bool:
    """Print how many queries' top-k ids equal the reference's."""
    import numpy as np
    n_same = int(np.sum(np.all(ref == ids, axis=1)))
    print(f"{name} ids == {ref_name} ids: {n_same} of {len(ref)} queries")
    return n_same == len(ref)


def ids_of(results):
    import numpy as np
    return np.stack([np.asarray(r.ids) for r in results])


def one_chip(seed: int, clock: CompileClock, n: int = N_SIFT1M,
             n_queries: int = N_QUERIES) -> None:
    import numpy as np

    from repro.core.engine import ground_truth, recall_at_k

    cfg, data, queries = sift1m_deployment(n, n_queries, seed)
    index = build(cfg, data, seed, clock)
    t0 = time.perf_counter()
    gt = ground_truth(data, queries, cfg.top_k)
    print(f"ground truth: {time.perf_counter() - t0:.2f} s host wall "
          "(exact, numpy)")
    dense, _ = serve(index, queries, clock, "dense")
    recall = recall_at_k(ids_of(dense), gt, cfg.top_k)
    print(f"recall@{cfg.top_k}: {recall:.4f} over {len(queries)} queries")
    check(recall >= 0.9, f"recall@10 {recall:.4f} < 0.9")
    fused, _ = serve(index, queries, clock, "fused", fused=True)
    check(agree("fused", ids_of(dense), ids_of(fused), "dense"),
          "fused top-k ids differ from the dense scan's")


def placement_rows(executor):
    """{device id: PQ-code rows} of the executor's HBM-tier placement."""
    placed = executor._placed           # set at the executor's first scan
    return {s.device.id: s.data.shape[0] for s in placed.addressable_shards}


def four_chips(seed: int, clock: CompileClock, n: int = N_SIFT1M,
               n_queries: int = N_QUERIES) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_test_mesh

    cfg, data, queries = sift1m_deployment(n, n_queries, seed)
    index = build(cfg, data, seed, clock)
    mesh = make_test_mesh(4)
    plan = index.plan(window=8)
    since = clock.snapshot()
    ref = ids_of(index.make_executor().run(queries, plan))
    clock.report("one-device reference", since)

    got = {}
    sharded = index.make_executor(mesh)
    for name, p in (("sharded dense", plan),
                    ("sharded fused", index.plan(window=8, fused=True))):
        since = clock.snapshot()
        got[name] = ids_of(sharded.run(queries, p))
        clock.report(name, since)
    rows = placement_rows(sharded)
    print(f"sharded placement (device id: code rows): {rows}")
    check(len(rows) == 4 and len(set(rows.values())) == 1,
          "the codes are not row-sharded evenly over 4 devices")

    resps, router = serve(index, queries, clock, "4 replicas",
                          n_replicas=4, mesh=mesh)
    got["4 replicas"] = ids_of(resps)
    groups = [placement_rows(svc.executor) for svc in router.replicas]
    print(f"replica placements (device id: code rows): {groups}")
    check(sorted(d for g in groups for d in g) == sorted(d.id for d in
                                                         jax.devices()),
          "the 4 replicas do not each hold the codes on their own device")
    same = [agree(name, ref, ids, "one-device") for name, ids in got.items()]
    check(all(same), "top-k ids differ from the one-device executor")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}: run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devs[0].platform!r}; nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    clock = CompileClock(jax)
    print(f"device: {devs[0].device_kind} x{len(devs)}, compile cache "
          f"{cache_dir}")
    print(f"N: {N_SIFT1M} (SIFT1M / BIGANN-1M size), {N_QUERIES} queries "
          f"from the same mixture, seed {args.seed}")
    print("cut: float32 storage (SIFT is uint8; native uint8 vectors are "
          "ROADMAP Queue 2)")
    print("cut: n_posting_fraction=0.02, as the benchmarks use: keeps the "
          "nav graph on its exact-kNN path")
    print("scan: xla (the TPU compiler refuses the Pallas ADC kernels)")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed, clock)
    stats = devs[0].memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"total: {time.perf_counter() - t0:.2f} s host wall")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
