"""End-to-end serving driver (the paper's kind): build a FusionANNS index
and serve batched query traffic, reporting recall / simulated-I/O / modelled
QPS-vs-threads — the full online pipeline of paper §3.

    PYTHONPATH=src python examples/serve_anns.py --n 30000 --queries 64

``--edge PORT`` instead serves the index over HTTP (the PR-7 front door:
tenant auth, request coalescing, elastic autoscaling) and fires a few demo
requests at itself; add ``--hold`` to keep serving until Ctrl-C so you can
drive it yourself:

    PYTHONPATH=src python examples/serve_anns.py --edge 8080 --hold
    curl -s -X POST http://127.0.0.1:8080/v1/search \\
      -H 'x-api-key: demo-key' -H 'content-type: application/json' \\
      -d "{\\"query\\": $(python -c 'print([0.1]*96)'), \\"k\\": 10}"
    curl -s http://127.0.0.1:8080/v1/stats -H 'x-api-key: demo-key'
"""

import argparse
import dataclasses
import json
import time

import numpy as np

from repro.configs.anns_datasets import SIFT_SMALL
from repro.core.engine import FusionANNSIndex, ground_truth, recall_at_k
from repro.core.perf_model import DeviceModel, QueryDemand, sweep_threads
from repro.data.synthetic import clustered_vectors
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30_000)
    ap.add_argument("--dim", type=int, default=96)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--producers", type=int, default=4,
                    help="submitter threads for the threaded-service demo")
    ap.add_argument("--replicas", type=int, default=2,
                    help="serving replicas behind the JSQ router demo")
    ap.add_argument("--inflight", type=int, default=64,
                    help="AsyncANNSClient max in-flight requests")
    ap.add_argument("--policy", default="jsq",
                    choices=("round_robin", "jsq", "deadline"),
                    help="ReplicaRouter routing policy")
    ap.add_argument("--edge", type=int, default=None, metavar="PORT",
                    help="serve over HTTP on this port instead of running "
                         "the in-process demos (see module docstring)")
    ap.add_argument("--hold", action="store_true",
                    help="with --edge: keep serving until Ctrl-C")
    args = ap.parse_args()

    cfg = dataclasses.replace(SIFT_SMALL, n_vectors=args.n, dim=args.dim,
                              pq_m=args.dim // 4, n_posting_fraction=0.02,
                              top_m=24, top_n=256)
    rng = np.random.default_rng(0)
    everything = clustered_vectors(rng, args.n + args.queries, args.dim,
                                   n_clusters=max(16, args.n // 400))
    data, queries = everything[:args.n], everything[args.n:]

    t0 = time.time()
    index = FusionANNSIndex.build(data, cfg)
    print(f"# build {time.time()-t0:.1f}s")
    if args.edge is not None:
        serve_edge(index, queries, args)
        return
    gt = ground_truth(data, queries, 10)

    # futures-first path: host traversal + async dispatch of the first
    # inflight_depth windows happens inside submit(); results() pipelines
    # each window's rerank against the next windows' in-flight scans
    t0 = time.time()
    ticket = index.submit(queries, window=1, inflight_depth=2)
    results = ticket.results()
    wall = time.time() - t0
    rec = recall_at_k(np.stack([r.ids for r in results]), gt, 10)

    # serving front-end on the same API: typed requests in, typed
    # responses out (SearchRequest -> QueryFuture -> SearchResponse)
    from repro.serve.anns_service import BatchingANNSService
    from repro.serve.client import (ANNSClient, AsyncANNSClient,
                                    SearchRequest)
    svc = BatchingANNSService(index, max_batch=16, max_wait_s=0.0,
                              scan_window=8, inflight_depth=2)
    futs = [svc.submit(SearchRequest(query=q, tag=i))
            for i, q in enumerate(queries)]
    svc.drain()
    assert all(f.done() for f in futs)
    pct = svc.latency_percentiles()

    # shared producer harness for the threaded-service and router demos:
    # N submitter threads behind the sync client (which blocks through
    # backpressure instead of surfacing BackpressureError)
    import threading

    def drive_producers(backend):
        client = ANNSClient(backend)

        def produce(i):
            client.search_many(
                [SearchRequest(query=q)
                 for q in queries[i::args.producers]], timeout=300)

        workers = [threading.Thread(target=produce, args=(i,))
                   for i in range(args.producers)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()

    # threaded runtime: a pump thread + out-of-order ticker per replica,
    # traffic from N producer threads (the deployment shape — DESIGN.md
    # §"Threading model")
    tsvc = BatchingANNSService(index, max_batch=16, max_wait_s=0.0005,
                               scan_window=8, inflight_depth=2,
                               threaded=True)
    drive_producers(tsvc)
    tsvc.stop()
    tpct = tsvc.latency_percentiles()

    # multi-replica routing: N threaded replicas behind one futures-first
    # submit() (each replica would own a disjoint sub-mesh on a multi-chip
    # host — launch.mesh.split_mesh; on one device the router is a pure
    # concurrency layer)
    from repro.core.perf_model import sweep_replicas
    from repro.serve.stack import make_serving_stack
    router = make_serving_stack(index, n_replicas=args.replicas,
                                policy=args.policy)
    drive_producers(router)

    # the asyncio front door (DESIGN.md §6): ONE event loop drives the
    # whole workload over the same router — thousands of in-flight
    # coroutines instead of a thread per producer; backpressure is an
    # awaited admission, never an exception
    import asyncio

    async def drive_async():
        async with AsyncANNSClient(router,
                                   max_inflight=args.inflight) as client:
            reqs = [SearchRequest(query=q, tag=i)
                    for i, q in enumerate(queries)]
            t0 = time.perf_counter()
            lat = [r.latency_s async for r in client.search_many(reqs)]
            return (time.perf_counter() - t0, lat, dict(client.stats))

    awall, alat, astats = asyncio.run(drive_async())
    router.stop()
    rpct = router.latency_percentiles()
    rollup = router.stats_rollup()
    rsweep = sweep_replicas(router.measured_demand(), DeviceModel(),
                            (1, args.replicas, 2 * args.replicas))

    stats = [r.stats for r in results]
    demand = QueryDemand(
        ssd_ios=float(np.mean([s.ios for s in stats])),
        ssd_bytes=float(np.mean([s.ssd_bytes for s in stats])),
        h2d_bytes=float(np.mean([s.h2d_bytes for s in stats])),
        gpu_lookups=float(np.mean([s.candidates_scanned for s in stats]))
        * cfg.pq_m,
        cpu_dist_ops=float(np.mean([s.rerank_scored for s in stats]))
        * args.dim,
        graph_hops=2.0 * cfg.top_m)
    sweep = sweep_threads(demand, DeviceModel())

    print(json.dumps({
        "recall@10": round(rec, 4),
        "host_wall_ms_per_query": round(1e3 * wall / len(queries), 2),
        "mean_ssd_ios": round(demand.ssd_ios, 1),
        "mean_h2d_bytes": int(demand.h2d_bytes),
        "early_stop_rate": round(float(np.mean(
            [s.early_stopped for s in stats])), 3),
        "service_p50_ms": round(pct["p50"] * 1e3, 2),
        "service_p99_ms": round(pct["p99"] * 1e3, 2),
        "threaded_p50_ms": round(tpct["p50"] * 1e3, 2),
        "threaded_p99_ms": round(tpct["p99"] * 1e3, 2),
        "threaded_producers": args.producers,
        "router_policy": args.policy,
        "router_replicas": args.replicas,
        "router_p50_ms": round(rpct["p50"] * 1e3, 2),
        "router_p99_ms": round(rpct["p99"] * 1e3, 2),
        "router_routed": rollup["routed"],
        "router_spills": rollup["spills"],
        "async_client_wall_ms": round(awall * 1e3, 1),
        "async_client_p50_ms": round(
            float(np.percentile(alat, 50)) * 1e3, 2),
        "async_client_p99_ms": round(
            float(np.percentile(alat, 99)) * 1e3, 2),
        "async_client_admission_waits": astats["admission_waits"],
        "router_modelled_qps": {f"r{n}": round(v)
                                for n, v in rsweep.items()},
        "modelled_qps": {f"t{t}": round(v["qps"]) for t, v in sweep.items()},
        "modelled_latency_ms": {f"t{t}": round(v["latency_ms"], 2)
                                for t, v in sweep.items()},
    }, indent=2))


def serve_edge(index, queries, args) -> None:
    """The PR-7 deployment shape: HTTP edge -> coalescing async client ->
    elastic JSQ router, with the autoscaler re-carving replicas under
    load.  Fires a few requests at itself so a bare run shows the whole
    path; ``--hold`` keeps the server up for external curl traffic."""
    import asyncio

    from repro.serve.autoscaler import ReplicaAutoscaler
    from repro.serve.edge import (AnnsEdge, EdgeConfig, HttpConn,
                                  TenantConfig)
    from repro.serve.stack import make_serving_stack

    router = make_serving_stack(index, n_replicas=args.replicas,
                                policy=args.policy)
    scaler = ReplicaAutoscaler(router, min_replicas=1,
                               max_replicas=2 * args.replicas).start()

    async def run() -> None:
        cfg = EdgeConfig(port=args.edge,
                         tenants=[TenantConfig("demo", "demo-key",
                                               rate_qps=0.0)],
                         max_inflight=args.inflight)
        async with AnnsEdge(router, cfg, own_backend=True) as edge:
            print(f"# edge serving on http://{cfg.host}:{edge.port} "
                  f"(x-api-key: demo-key)")
            conn = await HttpConn.open(cfg.host, edge.port)
            for i, q in enumerate(queries[:4]):
                status, doc = await conn.request(
                    "POST", "/v1/search",
                    {"query": q.tolist(), "k": 10, "tag": i},
                    {"x-api-key": "demo-key"})
                print(f"# HTTP {status} tag={doc['tag']} "
                      f"ids[:5]={doc['ids'][:5]}")
            _, stats = await conn.request("GET", "/v1/stats")
            print(json.dumps(stats, indent=2))
            await conn.aclose()
            if args.hold:
                print("# serving until Ctrl-C ...")
                try:
                    await asyncio.Event().wait()
                except (KeyboardInterrupt, asyncio.CancelledError):
                    pass

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    finally:
        scaler.stop()


if __name__ == "__main__":
    main()
