"""Segmented streaming index (DESIGN.md §10): delta segment semantics,
epoch-stamped view publication, background compaction, and snapshot
checkpoint/restore parity."""

import copy
import json
import time

import numpy as np
import pytest

from repro.core.engine import FusionANNSIndex
from repro.core.segments import DeltaSegment, IndexView


@pytest.fixture()
def index_and_data(anns_bundle, fresh_index):
    b = anns_bundle
    return b.cfg, b.data, b.new_vecs, b.queries, fresh_index


# ---------------------------------------------------------------------------
# DeltaSegment
# ---------------------------------------------------------------------------

def test_delta_segment_is_functional():
    d0 = DeltaSegment.empty(100, 4)
    d1 = d0.append(np.ones((3, 4), np.float32))
    assert len(d0) == 0 and len(d1) == 3          # d0 untouched
    assert d1.ids.tolist() == [100, 101, 102]
    d2 = d1.tombstone(np.array([1]))
    assert not d1.tombstoned.any()                # d1 untouched
    assert d2.tombstoned.tolist() == [False, True, False]
    assert d2.live_count() == 2
    d3 = d2.drop_prefix(2)
    assert d3.base == 102 and d3.ids.tolist() == [102]


def test_delta_scan_is_exact_squared_l2():
    d = DeltaSegment.empty(10, 3).append(
        np.array([[1, 0, 0], [0, 2, 0]], np.float32))
    ids, dists = d.scan(np.zeros(3, np.float32))
    assert ids.tolist() == [10, 11]
    np.testing.assert_allclose(dists, [1.0, 4.0])
    ids2, _ = d.tombstone(np.array([0])).scan(np.zeros(3, np.float32))
    assert ids2.tolist() == [11]


# ---------------------------------------------------------------------------
# View publication
# ---------------------------------------------------------------------------

def test_views_are_immutable_and_epoch_stamped(index_and_data):
    cfg, data, new_vecs, queries, index = index_and_data
    v0 = index.view()
    ids = index.insert(new_vecs)
    v1 = index.view()
    assert v1 is not v0 and v1.epoch == v0.epoch + 1
    assert len(v0.delta) == 0 and len(v1.delta) == len(new_vecs)
    assert v1.n_total == v0.n_total + len(new_vecs)
    index.delete(ids[:1])
    v2 = index.view()
    assert v2.epoch == v1.epoch + 1
    assert not v1.delta.tombstoned.any()          # old view untouched
    assert v2.delta.tombstoned[0]
    index.compact()
    v3 = index.view()
    assert v3.epoch == v2.epoch + 1
    assert v1.codes.shape[0] == v0.n_sealed       # old binding preserved
    # seal-time purge (PR 10): the row tombstoned in the delta is DROPPED
    # at the seal instead of being encoded — one fewer physical row than
    # sealed ids
    assert v3.codes.shape[0] == v0.n_sealed + len(new_vecs) - 1
    assert v3.n_rows == v3.codes.shape[0]
    assert v3.n_sealed == v0.n_sealed + len(new_vecs)   # ids never recycle


def test_candidate_ids_never_exceed_sealed_prefix(index_and_data):
    cfg, data, new_vecs, queries, index = index_and_data
    index.insert(new_vecs)
    view = index.view()
    for q in queries:
        ids = view.candidate_ids(q, cfg.top_m)
        if len(ids):
            assert ids.max() < view.n_sealed == view.codes.shape[0]


def test_compaction_purges_tombstoned_delta_rows(index_and_data):
    """Rows tombstoned before the seal never enter the posting lists.
    Posting members are physical ROW indices since the PR-10 purge; the
    view's ``id_of`` maps them back to global ids."""
    cfg, data, new_vecs, queries, index = index_and_data
    ids = index.insert(new_vecs)
    index.delete(ids[:3])
    index.compact()
    view = index.view()
    member_ids = view.id_of[np.concatenate(index.posting.members)]
    assert not (set(ids[:3].tolist()) & set(member_ids.tolist()))
    # surviving rows ARE reachable through the sealed tiers
    assert set(ids[3:].tolist()) <= set(member_ids.tolist())


def test_seal_time_purge_accounting(index_and_data):
    """The purge's whole ledger: physical rows, SSD rows, id maps, and
    the n_sealed/n_rows split all agree after sealing a delta with
    tombstoned rows — and purged ids stay tombstoned forever (they can
    never resurface through row arithmetic)."""
    cfg, data, new_vecs, queries, index = index_and_data
    n0 = index.view().n_sealed
    ids = index.insert(new_vecs)
    index.delete(ids[5:9])                         # 4 of 20 purged at seal
    sealed = index.compact()
    assert sealed == len(new_vecs)                 # delta rows consumed
    view = index.view()
    n_live = len(new_vecs) - 4
    assert view.n_sealed == n0 + len(new_vecs)
    assert view.n_rows == n0 + n_live
    assert view.codes.shape[0] == view.n_rows
    assert len(index.ssd.vectors) >= view.n_rows   # SSD rows track rows,
    #                                                not ids
    # id_of is strictly increasing (order-preserving seal) and row_of is
    # its exact inverse, with purged ids mapped to -1
    assert (np.diff(view.id_of) > 0).all()
    np.testing.assert_array_equal(view.row_of[view.id_of],
                                  np.arange(view.n_rows))
    assert (view.row_of[ids[5:9]] == -1).all()
    assert view.tombstones[ids[5:9]].all()
    # survivors stay queryable under their ORIGINAL global ids
    for j in list(range(5)) + list(range(9, len(new_vecs))):
        assert int(index.query(new_vecs[j], k=1).ids[0]) == int(ids[j])
    # purged ids never appear in any result
    for q in list(queries[:4]) + list(new_vecs[5:9]):
        got = index.query(q, k=10).ids
        assert not (set(got.tolist()) & set(ids[5:9].tolist()))


def test_concurrent_compact_serializes(index_and_data):
    cfg, data, new_vecs, queries, index = index_and_data
    index.insert(new_vecs)
    assert index.compact(wait=False) == len(new_vecs)
    assert index.compact(wait=False) == 0          # nothing left to seal


def test_background_compactor_seals_while_serving(index_and_data):
    cfg, data, new_vecs, queries, index = index_and_data
    index.start_compactor(min_delta=8, poll_s=0.01)
    try:
        ids = index.insert(new_vecs)               # 20 >= threshold
        deadline = time.time() + 20.0
        while index.delta_size and time.time() < deadline:
            index.query(queries[0], k=5)           # serve during the seal
            time.sleep(0.01)
        assert index.delta_size == 0
        assert index.codes.shape[0] == index.n_total
        hits = sum(int(index.query(v, k=1).ids[0] == nid)
                   for v, nid in zip(new_vecs, ids))
        assert hits >= 18
    finally:
        index.stop_compactor()


def test_deepcopy_gets_fresh_locks_and_no_compactor(index_and_data):
    cfg, data, new_vecs, queries, index = index_and_data
    index.start_compactor(min_delta=10**6)
    try:
        clone = copy.deepcopy(index)
    finally:
        index.stop_compactor()
    assert clone._compactor is None
    assert clone._mut_lock is not index._mut_lock
    clone.insert(new_vecs)
    assert clone.delta_size == len(new_vecs) and index.delta_size == 0


# ---------------------------------------------------------------------------
# Snapshots
# ---------------------------------------------------------------------------

def _assert_bit_identical(a: FusionANNSIndex, b: FusionANNSIndex, queries):
    for q in queries:
        ra, rb = a.query(q), b.query(q)
        np.testing.assert_array_equal(ra.ids, rb.ids)
        np.testing.assert_array_equal(ra.dists, rb.dists)


@pytest.mark.parametrize("legacy_use_kernel", [None, True])
def test_snapshot_roundtrip_sealed_only(index_and_data, tmp_path,
                                        legacy_use_kernel):
    """``legacy_use_kernel``: manifests written before the scan was chosen
    by platform carry a ``use_kernel`` field; they still load."""
    cfg, data, new_vecs, queries, index = index_and_data
    index.save_snapshot(str(tmp_path / "snap"))
    if legacy_use_kernel is not None:
        path = tmp_path / "snap" / "manifest.json"
        manifest = json.loads(path.read_text())
        manifest["use_kernel"] = legacy_use_kernel
        path.write_text(json.dumps(manifest))
    restored = FusionANNSIndex.load_snapshot(str(tmp_path / "snap"))
    assert restored.epoch == index.epoch
    assert restored.n_total == index.n_total
    _assert_bit_identical(index, restored, queries)


def test_snapshot_roundtrip_with_delta_and_tombstones(index_and_data,
                                                      tmp_path):
    """The acceptance bar: a replica restored from save_snapshot returns
    bit-identical top-k ids to the live index it was taken from — sealed
    tiers, unsealed delta rows, and tombstones in both segments."""
    cfg, data, new_vecs, queries, index = index_and_data
    ids = index.insert(new_vecs[:12])
    index.compact()                                # some sealed inserts
    ids2 = index.insert(new_vecs[12:])             # plus a live delta
    index.delete(np.array([ids[0], ids2[0], 3]))   # both segments + base
    index.save_snapshot(str(tmp_path / "snap"))
    restored = FusionANNSIndex.load_snapshot(str(tmp_path / "snap"))
    assert restored.epoch == index.epoch
    assert restored.delta_size == index.delta_size == len(new_vecs) - 12
    _assert_bit_identical(index, restored, queries)
    _assert_bit_identical(index, restored, new_vecs)
    # and the restored copy keeps evolving correctly on its own
    both = [index, restored]
    for ix in both:
        ix.insert(new_vecs[:4])
        ix.compact()
    _assert_bit_identical(index, restored, queries)


def test_snapshot_excludes_unpublished_ssd_rows(index_and_data, tmp_path):
    """save during the compaction gap: the SSD tier is truncated to the
    captured view's sealed prefix, so restore + compact never duplicates
    rows."""
    cfg, data, new_vecs, queries, index = index_and_data
    index.insert(new_vecs)
    index.save_snapshot(str(tmp_path / "snap"))
    restored = FusionANNSIndex.load_snapshot(str(tmp_path / "snap"))
    assert len(restored.ssd.vectors) == restored.view().n_sealed
    restored.compact()
    index.compact()
    assert len(restored.ssd.vectors) == len(index.ssd.vectors)
    _assert_bit_identical(index, restored, new_vecs)


def test_stack_boots_from_snapshot(index_and_data, tmp_path):
    from repro.serve.client import as_request
    from repro.serve.stack import make_serving_stack
    cfg, data, new_vecs, queries, index = index_and_data
    index.insert(new_vecs)
    index.save_snapshot(str(tmp_path / "snap"))
    want = [index.query(q, k=5).ids for q in queries[:4]]
    router = make_serving_stack(index=None, n_replicas=2, threaded=False,
                                snapshot_dir=str(tmp_path / "snap"))
    try:
        futs = [router.submit(as_request(q, k=5)) for q in queries[:4]]
        router.drain()
        got = [f.result().ids for f in futs]
    finally:
        router.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
