"""Multi-replica routing over one mesh (ISSUE 4 acceptance).

Contract under test:
* every routing policy (round_robin / jsq / deadline) returns
  BIT-IDENTICAL ids to a single-replica ``run()`` — routing is a
  scheduling choice, never a result knob;
* 8 producer threads across 2 threaded replicas: id parity, zero leaked
  futures after ``stop()``, empty queues on every replica;
* JSQ probe: a saturated replica (its serve path gated on an event, so
  the probe does not depend on scheduler luck) is bypassed — all routed
  traffic lands on the idle replica;
* deadline policy: a request carrying a deadline spills to the
  least-loaded replica while deadline-free traffic follows round-robin
  into the loaded one;
* the fig9 ``router_jsq`` model: QPS on the demand measured THROUGH the
  router increases strictly monotonically from 1 -> 2 -> 4 replicas;
* updates propagate to every replica (test_updates semantics under
  routing);
* ``split_mesh`` carves one mesh into disjoint device groups and the
  routed sub-mesh scan matches the single-device scan exactly
  (subprocess with forced host devices, like test_executor's).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.futures import BackpressureError
from repro.core.perf_model import DeviceModel, sweep_replicas
from repro.serve.client import SearchRequest
from repro.serve.router import POLICIES, ReplicaRouter


# ------------------------------------------------------------------ parity

@pytest.mark.parametrize("policy", POLICIES)
def test_policy_parity_with_single_replica_run(anns_bundle, policy):
    """Each policy, mixed k, 2 sync replicas: ids == index.query()."""
    b = anns_bundle
    ks = [1, 3, 5, 7, 10, 2, 4, 6]
    router = ReplicaRouter(b.index, n_replicas=2, policy=policy,
                           threaded=False, max_batch=4, max_wait_s=0.0)
    futs = [router.submit(SearchRequest(
                query=q, k=ks[i % len(ks)],
                deadline_s=30.0 if i % 2 else None))
            for i, q in enumerate(b.queries)]
    router.drain()
    for i, (q, f) in enumerate(zip(b.queries, futs)):
        np.testing.assert_array_equal(
            f.result().ids,
            b.index.query(q, k=ks[i % len(ks)]).ids)
    roll = router.stats_rollup()
    assert sum(roll["routed"]) == len(b.queries)
    assert roll["requests"] == len(b.queries)
    # the QueryStats rollup saw every request's re-rank traffic and
    # graph search
    assert roll["query_stats"]["ios"] > 0
    assert roll["query_stats"]["rerank_scored"] > 0
    assert roll["query_stats"]["graph_expansions"] >= len(b.queries)


def test_round_robin_spreads_evenly(anns_bundle):
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="round_robin",
                           threaded=False, max_batch=4, max_wait_s=0.0)
    for q in b.queries[:8]:
        router.submit(SearchRequest(query=q))
    assert router.stats_rollup()["routed"] == [4, 4]
    router.drain()


# ------------------------------------------------------------------ stress

def test_router_stress_8_producers_2_replicas_zero_leaks(anns_bundle):
    b = anns_bundle
    n_producers, per_producer = 8, 5
    ks = [1, 3, 5, 10, 2, 7, 4, 6]
    router = ReplicaRouter(b.index, n_replicas=2, policy="jsq",
                           threaded=True, max_batch=8, max_wait_s=0.002,
                           scan_window=2, inflight_depth=2)
    futures = {}
    errors = []

    def producer(tid):
        for i in range(per_producer):
            qi = (tid * per_producer + i) % len(b.queries)
            k = ks[(tid + i) % len(ks)]
            while True:
                try:
                    futures[(tid, i)] = (qi, k, router.submit(
                        SearchRequest(query=b.queries[qi], k=k)))
                    break
                except BackpressureError:
                    time.sleep(1e-3)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results = {}
    for key, (qi, k, fut) in futures.items():
        try:
            results[key] = (qi, k, fut.result(timeout=120).ids)
        except Exception as exc:              # noqa: BLE001 — fail the test
            errors.append((key, exc))
    assert not errors, errors
    router.stop()

    # bit-identical ids to the single-replica synchronous path
    for qi, k, ids in results.values():
        np.testing.assert_array_equal(ids, b.index.query(b.queries[qi],
                                                         k=k).ids)
    # zero leaked futures / requests anywhere after the fan-out drain
    assert all(fut.done() for _, _, fut in futures.values())
    for svc in router.replicas:
        assert not svc._queue and svc._serving == 0
        assert svc._pump_thread is None and svc._ticker_thread is None
    assert sum(router.stats_rollup()["routed"]) == n_producers * per_producer


# --------------------------------------------------------------- JSQ probe

def test_jsq_bypasses_saturated_replica(anns_bundle):
    """Gate replica 0's serve path on an event, park 3 live requests on
    it, then route through JSQ: every routed request must land on the
    idle replica 1 (live-request count, not round-robin)."""
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="jsq",
                           threaded=True, max_batch=4, max_wait_s=0.001)
    svc0 = router.replicas[0]
    started, release = threading.Event(), threading.Event()
    orig = svc0._serve_batch_inner

    def gated(batch):
        started.set()
        assert release.wait(timeout=60)
        return orig(batch)

    svc0._serve_batch_inner = gated
    try:
        # saturate replica 0 below the router (its pump blocks in `gated`,
        # so its live_load stays at 3 for the whole probe)
        pre = [svc0.submit(SearchRequest(query=b.queries[i])) for i in range(3)]
        assert started.wait(timeout=60)
        assert svc0.live_load() == 3
        routed = []
        for q in b.queries[3:7]:
            fut = router.submit(SearchRequest(query=q))
            routed.append((q, fut.result(timeout=60).ids))
    finally:
        release.set()
    for f in pre:
        f.result(timeout=60)
    router.stop()
    assert router.stats_rollup()["routed"] == [0, 4]
    for q, ids in routed:
        np.testing.assert_array_equal(ids, b.index.query(q).ids)


def test_deadline_policy_spills_to_least_loaded(anns_bundle):
    """Deadline traffic jumps the round-robin line to the least-loaded
    replica; deadline-free traffic follows round-robin into the loaded
    one (sync harness: queues only drain when we say so)."""
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="deadline",
                           threaded=False, max_batch=8, max_wait_s=10.0)
    # park 3 live requests on replica 0, below the router
    pre = [router.replicas[0].submit(SearchRequest(query=q)) for q in b.queries[:3]]
    # round-robin cursor is at 0, but the deadline spills to replica 1
    spilled = router.submit(SearchRequest(query=b.queries[3], deadline_s=30.0))
    assert router.stats_rollup()["routed"] == [0, 1]
    assert router.stats_rollup()["deadline_spills"] == 1
    # deadline-free traffic keeps round-robin order: cursor moved to 1,
    # then wraps INTO the loaded replica 0
    router.submit(SearchRequest(query=b.queries[4]))
    router.submit(SearchRequest(query=b.queries[5]))
    assert router.stats_rollup()["routed"] == [1, 2]
    router.drain()
    np.testing.assert_array_equal(spilled.result().ids,
                                  b.index.query(b.queries[3]).ids)
    for q, f in zip(b.queries[:3], pre):
        np.testing.assert_array_equal(f.result().ids,
                                      b.index.query(q).ids)


# ---------------------------------------------------------- backpressure

def test_router_spills_on_backpressure_then_rejects(anns_bundle):
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="round_robin",
                           threaded=False, max_batch=8, max_wait_s=10.0,
                           max_queue=1)
    a = router.submit(SearchRequest(query=b.queries[0]))           # replica 0
    c = router.submit(SearchRequest(query=b.queries[1]))           # replica 1 (rr)
    assert router.stats_rollup()["routed"] == [1, 1]
    with pytest.raises(BackpressureError, match="all 2 replicas"):
        router.submit(SearchRequest(query=b.queries[2]))
    roll = router.stats_rollup()
    assert roll["rejected"] == 1
    router.drain()
    assert a.done() and c.done()
    # slots freed: admission works again
    d = router.submit(SearchRequest(query=b.queries[2]))
    router.drain()
    np.testing.assert_array_equal(d.result().ids,
                                  b.index.query(b.queries[2]).ids)


def test_spill_exhausted_counter_and_accounting_invariant(anns_bundle):
    """A spill chain that exhausts EVERY replica counts as
    ``spill_exhausted``, and the router's books always balance:
    ``submitted == sum(routed) + rejected`` — every submit() call is
    accounted exactly once, landed or rejected."""
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="round_robin",
                           threaded=False, max_batch=8, max_wait_s=10.0,
                           max_queue=1)
    router.submit(SearchRequest(query=b.queries[0]))
    router.submit(SearchRequest(query=b.queries[1]))
    for _ in range(3):                       # every replica full: reject
        with pytest.raises(BackpressureError):
            router.submit(SearchRequest(query=b.queries[2]))
    roll = router.stats_rollup()
    assert roll["rejected"] == 3
    assert roll["spill_exhausted"] == 3
    assert roll["submitted"] == sum(roll["routed"]) + roll["rejected"]
    router.drain()
    router.submit(SearchRequest(query=b.queries[2]))
    router.drain()
    roll = router.stats_rollup()
    assert roll["submitted"] == sum(roll["routed"]) + roll["rejected"] == 6


# ------------------------------------------------------------- elastic set

def test_add_and_remove_replica_round_trip(anns_bundle):
    """Grow 2 -> 3, serve on all three, shrink back: stable slot ids,
    growing routed ledger, drained victim, and the accounting invariant
    across the whole scaling history."""
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="round_robin",
                           threaded=False, max_batch=4, max_wait_s=0.0)
    slot = router.add_replica()
    assert slot == 2 and router.n_replicas == 3
    assert router.replica_ids == [0, 1, 2]
    futs = [router.submit(SearchRequest(query=q)) for q in b.queries[:9]]
    router.drain()
    assert router.stats_rollup()["routed"] == [3, 3, 3]
    removed = router.remove_replica()         # least-loaded: all idle -> 0
    assert removed == 0 and router.n_replicas == 2
    assert router.replica_ids == [1, 2]
    more = [router.submit(SearchRequest(query=q)) for q in b.queries[9:13]]
    router.drain()
    roll = router.stats_rollup()
    assert roll["routed"] == [3, 5, 5]        # slot 0 frozen, 1/2 grew
    assert roll["submitted"] == sum(roll["routed"]) + roll["rejected"]
    assert roll["scale_ups"] == 1 and roll["scale_downs"] == 1
    # percentiles still describe the whole stream (retired history kept)
    assert roll["requests"] == 13
    for q, f in zip(b.queries, futs + more):
        np.testing.assert_array_equal(f.result().ids,
                                      b.index.query(q).ids)
    with pytest.raises(ValueError, match="no replica with slot id"):
        router.remove_replica(0)              # already gone
    router.remove_replica(1)
    with pytest.raises(ValueError, match="last replica"):
        router.remove_replica()


def test_remove_replica_drains_victim_zero_leaks(anns_bundle):
    """Removal under live traffic: requests parked on the victim resolve
    (its pump drains them before exit) and no future leaks anywhere."""
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="round_robin",
                           threaded=True, max_batch=4, max_wait_s=0.001)
    futs = [router.submit(SearchRequest(query=q)) for q in b.queries[:8]]
    victim_slot = router.remove_replica(0)
    assert victim_slot == 0 and router.n_replicas == 1
    for q, f in zip(b.queries[:8], futs):
        np.testing.assert_array_equal(f.result(timeout=120).ids,
                                      b.index.query(q).ids)
    assert all(f.done() for f in futs)
    router.stop()
    roll = router.stats_rollup()
    assert roll["requests"] == 8              # retired history folded in
    assert roll["submitted"] == sum(roll["routed"]) + roll["rejected"]
    assert router.latency_percentiles()["n"] == 8


def test_scaling_signals_snapshot(anns_bundle):
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="jsq",
                           threaded=False, max_batch=8, max_wait_s=10.0)
    router.submit(SearchRequest(query=b.queries[0]))
    sig = router.scaling_signals()
    assert sig["n_replicas"] == 2 and sig["live_load"] == 1
    assert len(sig["per_replica_load"]) == 2
    assert sig["submitted"] == 1 and sig["rejected"] == 0
    router.drain()
    sig = router.scaling_signals()
    assert sig["live_load"] == 0 and sig["latency_n"] == 1


def test_recarve_mesh_unequal_groups():
    """recarve_mesh relaxes split_mesh's divisibility: 1 device still
    carves only into 1 group, and bad counts raise."""
    from repro.launch.mesh import make_test_mesh, recarve_mesh
    mesh = make_test_mesh(1)
    assert recarve_mesh(mesh, 1) == [mesh]
    with pytest.raises(ValueError, match="cannot carve"):
        recarve_mesh(mesh, 2)
    with pytest.raises(ValueError, match="n_groups"):
        recarve_mesh(mesh, 0)


# ------------------------------------------------------ fig9 replica model

def test_router_jsq_qps_model_monotonic_in_replicas(anns_bundle):
    """The fig9 ``router_jsq`` acceptance: on demand measured THROUGH the
    router, modelled QPS increases strictly 1 -> 2 -> 4 replicas."""
    b = anns_bundle
    router = ReplicaRouter(b.index, n_replicas=2, policy="jsq",
                           threaded=True, max_batch=8, max_wait_s=0.001)
    futs = [router.submit(SearchRequest(query=q)) for q in b.queries]
    for f in futs:
        f.result(timeout=120)
    router.stop()
    demand = router.measured_demand()
    assert demand.ssd_ios > 0 and demand.cpu_dist_ops > 0
    sweep = sweep_replicas(demand, DeviceModel(), (1, 2, 4))
    assert sweep[1] < sweep[2] < sweep[4], sweep


# ----------------------------------------------------------------- updates

def test_updates_propagate_to_every_replica(anns_bundle, fresh_index):
    """test_updates semantics hold under routing: inserts and tombstones
    land in the SHARED tiers, so both replicas see them (round-robin
    guarantees both actually serve post-update traffic)."""
    b = anns_bundle
    router = ReplicaRouter(fresh_index, n_replicas=2, policy="round_robin",
                           threaded=True, max_batch=4, max_wait_s=0.001)
    new_ids = router.insert(b.new_vecs)
    victim = new_ids[0]
    router.delete(np.array([victim]))
    futs = [router.submit(SearchRequest(query=v)) for v in b.new_vecs[:8]]
    responses = [f.result(timeout=120) for f in futs]
    router.stop()
    assert router.stats_rollup()["routed"] == [4, 4]
    for r in responses:
        assert victim not in set(r.ids.tolist())
    hits = sum(int(r.ids[0] == nid)
               for r, nid in zip(responses[1:], new_ids[1:8]))
    assert hits >= 5


# -------------------------------------------------------------- split_mesh

def test_split_mesh_validation():
    from repro.launch.mesh import make_test_mesh, split_mesh
    mesh = make_test_mesh(1)
    assert split_mesh(mesh, 1) == [mesh]          # identity
    with pytest.raises(ValueError, match="cannot split 1 device"):
        split_mesh(mesh, 2)
    with pytest.raises(ValueError, match="n_replicas"):
        split_mesh(mesh, 0)
    with pytest.raises(ValueError, match="n_replicas"):
        ReplicaRouter(None, n_replicas=0)
    with pytest.raises(ValueError, match="unknown policy"):
        ReplicaRouter(None, policy="nope")


_SUBMESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses, json
import numpy as np
from repro.configs.anns_datasets import SIFT_SMALL
from repro.core.engine import FusionANNSIndex
from repro.data.synthetic import clustered_vectors
from repro.launch.mesh import make_test_mesh, split_mesh
from repro.serve.client import SearchRequest
from repro.serve.router import ReplicaRouter

rng = np.random.default_rng(0)
cfg = dataclasses.replace(SIFT_SMALL, n_vectors=800, dim=32,
                          n_posting_fraction=0.02)
data = clustered_vectors(rng, 808, 32, n_clusters=8)
index = FusionANNSIndex.build(data[:800], cfg)
queries = data[800:]

mesh = make_test_mesh(4)
subs = split_mesh(mesh, 2)
dev_groups = [sorted(d.id for d in np.asarray(s.devices).ravel())
              for s in subs]
ref = [index.query(q, k=5).ids for q in queries]

router = ReplicaRouter(index, n_replicas=2, policy="jsq", mesh=mesh,
                       threaded=True, max_batch=4, max_wait_s=0.001)
shards = [svc.executor._n_shards() for svc in router.replicas]
futs = [router.submit(SearchRequest(query=q, k=5)) for q in queries]
ids = [f.result(timeout=120).ids for f in futs]
router.stop()

out = {
    "disjoint": not (set(dev_groups[0]) & set(dev_groups[1])),
    "covers": sorted(dev_groups[0] + dev_groups[1]) == [0, 1, 2, 3],
    "shards": shards,
    "parity": all(np.array_equal(a, b) for a, b in zip(ids, ref)),
    "served": int(sum(router.stats_rollup()["routed"])),
}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def submesh_results():
    """Sub-mesh routing needs >= 4 devices: host platform override BEFORE
    jax import (same pattern as test_executor's sharded fixture)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"    # a CPU rehearsal: never reach for a chip
    proc = subprocess.run(
        [sys.executable, "-c", _SUBMESH_SCRIPT, os.path.abspath(src)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_split_mesh_groups_are_disjoint_and_cover(submesh_results):
    assert submesh_results["disjoint"] and submesh_results["covers"]


def test_submesh_replica_scan_matches_single_device(submesh_results):
    assert submesh_results["shards"] == [2, 2]
    assert submesh_results["parity"], submesh_results
    assert submesh_results["served"] == 8
