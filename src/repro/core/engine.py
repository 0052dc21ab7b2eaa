"""FusionANNS engine: offline index build (§3 Offline) + the 8-step online
query pipeline (§3 Online).

Tier placement in this build (DESIGN.md §2):
  * navigation graph + posting-list vector-IDs  -> host numpy ("DRAM")
  * PQ codes + codebooks                        -> jax arrays ("HBM";
    sharded via core.distributed on a mesh)
  * raw vectors                                 -> SSDSim (4 KB page model)

Updates (DESIGN.md §10): the index is SEGMENTED.  The built tiers are
immutable sealed segments described by one epoch-stamped
:class:`~repro.core.segments.IndexView`; inserts land in a small mutable
delta segment (scanned exactly, merged after the PQ scan + re-rank),
deletes tombstone in the owning segment, and :meth:`compact` — usually
driven by the background :class:`~repro.core.segments.SegmentCompactor`
— seals the delta into the immutable tiers under the ``compaction``-
ranked witness lock.  Readers never lock: they pin ``index.view()`` once
per scan window.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.concurrency.witness import make_condition, make_lock
from repro.configs.base import ANNSConfig
from repro.core import clustering, navgraph as ng, pq
# QueryStats / QueryResult live in executor.py now; re-exported here so
# ``from repro.core.engine import QueryResult`` keeps working.
from repro.core.executor import (PlanOverrides, QueryExecutor,  # noqa: F401
                                 QueryPlan, QueryResult, QueryStats)
from repro.core.filters import AttributeTable
from repro.core.futures import BatchTicket, QueryFuture  # noqa: F401
from repro.core.io_sim import IOStats, SSDSim, StorageLayout
from repro.core.segments import DeltaSegment, IndexView, SegmentCompactor

# v2 (DESIGN.md §11): + per-row attribute columns and the seal-time purge
# id map (``id_of``).  v1 snapshots still load — identity id map, no
# attributes.
SNAPSHOT_FORMAT_VERSION = 2
_SNAPSHOT_COMPAT_VERSIONS = (1, 2)
_SNAPSHOT_MANIFEST = "manifest.json"
_SNAPSHOT_ARRAYS = "arrays.npz"


class FusionANNSIndex:
    """The four-tier index with segmented streaming updates.

    Immutable-per-epoch state (codes, posting lists, sealed tombstones,
    nav graph, delta segment) lives in ``self._view`` — an
    :class:`IndexView` published by one atomic reference assignment under
    ``_mut_lock`` (rank ``compaction``).  Readers access it lock-free via
    :meth:`view` / the compatibility properties below; mutators
    (:meth:`insert`, :meth:`delete`, :meth:`compact`) never let a reader
    observe torn multi-tier state because every published view's tiers
    describe exactly the same id range.
    """

    def __init__(self, cfg: ANNSConfig, codebook: pq.PQCodebook,
                 codes: jax.Array, posting: clustering.PostingLists,
                 graph: ng.NavGraph, ssd: SSDSim,
                 rotation: Optional[np.ndarray] = None,
                 tombstones: Optional[np.ndarray] = None,
                 attributes=None, id_of: Optional[np.ndarray] = None):
        self.cfg = cfg
        self.codebook = codebook                 # HBM tier
        self.ssd = ssd                           # SSD tier: raw vectors
        # beyond-paper: OPQ rotation (core/opq.py); applied to queries
        # before the LUT build only — clustering/graph/re-rank raw space.
        self.rotation = rotation
        # id-space size: with a seal-time-purged snapshot the tombstone
        # array covers MORE ids than there are physical code rows
        n_ids = (int(codes.shape[0]) if tombstones is None
                 else int(len(tombstones)))
        tomb = (np.zeros(n_ids, bool) if tombstones is None
                else np.asarray(tombstones, bool))
        self._mut_lock = make_lock("compaction")
        self._mut_cond = make_condition("compaction", self._mut_lock)
        self._compacting = False                 # guarded-by: _mut_lock
        self._compactor: Optional[SegmentCompactor] = None
        dim = int(ssd.vectors.shape[1])
        attrs = (AttributeTable.from_columns(n_ids, attributes)
                 if attributes else None)
        self._view = IndexView(
            epoch=0, codes=codes, posting=posting, tombstones=tomb,
            graph=graph, delta=DeltaSegment.empty(n_ids, dim),
            attrs=attrs, id_of=id_of)

    # deepcopy/pickle: locks and threads are per-process; a copy starts
    # with fresh ones (and no background compactor)
    def __getstate__(self):
        state = self.__dict__.copy()
        for key in ("_mut_lock", "_mut_cond", "_compactor", "_executor"):
            state.pop(key, None)
        state["_compacting"] = False
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._mut_lock = make_lock("compaction")
        self._mut_cond = make_condition("compaction", self._mut_lock)
        self._compactor = None

    # ------------------------------------------------------ view plumbing
    def view(self) -> IndexView:
        """Pin the current epoch's consistent binding of every tier.
        Lock-free: one attribute read of an atomically-published ref."""
        return self._view

    @property
    def epoch(self) -> int:
        """Bumped by every successful insert/delete/compact publish; the
        coalescer keys on it so waiters never attach across a mutation."""
        return self._view.epoch

    @property
    def codes(self) -> jax.Array:
        return self._view.codes

    @property
    def posting(self) -> clustering.PostingLists:
        return self._view.posting

    @property
    def tombstones(self) -> np.ndarray:
        return self._view.tombstones

    @property
    def graph(self) -> ng.NavGraph:
        return self._view.graph

    @property
    def n_total(self) -> int:
        return self._view.n_total

    @property
    def delta_size(self) -> int:
        return len(self._view.delta)

    def _lut_query(self, q: np.ndarray) -> np.ndarray:
        return q @ self.rotation if self.rotation is not None else q

    # ------------------------------------------------------------------ build
    @staticmethod
    def build(data: np.ndarray, cfg: ANNSConfig, seed: int = 0,
              *, intra_merge: bool = True, use_buffer: bool = True,
              optimized_layout: bool = True,
              use_opq: bool = False,
              attributes=None) -> "FusionANNSIndex":
        n, d = data.shape
        rng = np.random.default_rng(seed)
        key = jax.random.key(seed)
        # 1. posting lists (hierarchical balanced clustering + Eq.2 replicas)
        n_clusters = max(4, int(n * cfg.n_posting_fraction))
        posting = clustering.build_posting_lists(
            rng, data.astype(np.float32), n_clusters,
            eps=cfg.replication_eps, max_replicas=cfg.max_replicas)
        # 2. navigation graph over centroids (DRAM)
        graph = ng.build_navgraph(posting.centroids, degree=cfg.graph_degree)
        # 3. PQ codes pinned in HBM (optionally OPQ-rotated — beyond-paper)
        rotation = None
        if use_opq:
            from repro.core.opq import train_opq
            ocb, _ = train_opq(key, data, cfg.pq_m, cfg.pq_nbits)
            cb, rotation = ocb.cb, ocb.rotation
            codes = pq.encode(cb, jnp.asarray(
                data.astype(np.float32) @ rotation))
        else:
            cb = pq.train_codebooks(key, jnp.asarray(data, jnp.float32),
                                    cfg.pq_m, cfg.pq_nbits)
            codes = pq.encode(cb, jnp.asarray(data, jnp.float32))
        # 4. raw vectors on SSD, bucketed by primary centroid (§4.3)
        layout = StorageLayout.build(
            posting.primary, posting.n_clusters,
            vec_bytes=data.dtype.itemsize * d, page_bytes=cfg.page_bytes,
            optimized=optimized_layout)
        ssd = SSDSim(data, layout, buffer_pages=cfg.dram_buffer_pages,
                     intra_merge=intra_merge, use_buffer=use_buffer)
        # NOTE: intermediate posting-list *contents* are discarded here —
        # only the ID metadata survives in DRAM (paper §4.1).
        return FusionANNSIndex(cfg=cfg, codebook=cb, codes=codes,
                               posting=posting, graph=graph, ssd=ssd,
                               rotation=rotation, attributes=attributes)

    # --------------------------------------------------------------- updates
    def insert(self, vectors: np.ndarray,
               attributes=None) -> np.ndarray:
        """Append vectors to the delta segment; returns their new ids.

        ``attributes`` maps column name -> per-row ints (filtered search,
        DESIGN.md §11); columns absent here backfill UNSET and never
        match a predicate.  O(rows) — no clustering, PQ encode, or SSD
        traffic here; sealing is compaction's job.  The ids are published
        atomically WITH the rows (one view swap), so a concurrent query
        either sees none of the batch or a fully-consistent binding of
        all of it — never ids pointing past the end of any tier (the
        pre-segmentation race).
        """
        vecs = np.atleast_2d(np.asarray(vectors, np.float32))
        with self._mut_cond:  # acquires: compaction
            cur = self._view
            new_ids = np.arange(cur.n_total, cur.n_total + len(vecs),
                                dtype=np.int64)
            self._view = dataclasses.replace(
                cur, epoch=cur.epoch + 1,
                delta=cur.delta.append(vecs, attributes))
            self._mut_cond.notify_all()          # wake the compactor
        return new_ids

    def delete(self, ids: np.ndarray) -> None:
        """Tombstone ids in their owning segment (sealed array copy-on-
        write, or a functional delta update).  Deleting an id that was
        never published (``>= n_total``) raises ``ValueError`` instead of
        silently corrupting a tombstone array that does not cover it."""
        idarr = np.atleast_1d(np.asarray(ids, np.int64))
        with self._mut_cond:  # acquires: compaction
            cur = self._view
            if len(idarr) and (int(idarr.min()) < 0
                               or int(idarr.max()) >= cur.n_total):
                bad = idarr[(idarr < 0) | (idarr >= cur.n_total)]
                raise ValueError(
                    f"delete: id(s) {bad[:8].tolist()} not published — "
                    f"index currently holds ids [0, {cur.n_total})")
            sealed = idarr[idarr < cur.n_sealed]
            local = idarr[idarr >= cur.n_sealed] - cur.delta.base
            tomb = cur.tombstones
            if len(sealed):
                tomb = tomb.copy()
                tomb[sealed] = True
            delta = cur.delta.tombstone(local) if len(local) else cur.delta
            self._view = dataclasses.replace(
                cur, epoch=cur.epoch + 1, tombstones=tomb, delta=delta)

    def compact(self, *, wait: bool = True) -> int:
        """Seal the current delta into the immutable tiers.  Returns the
        number of rows sealed (0 if the delta was empty, or if another
        thread is already compacting and ``wait=False``).

        Three phases: (1) claim — snapshot the delta prefix under the
        lock and take the single-compactor token; (2) seal — re-cluster,
        PQ-encode, and extend the SSD tier OUTSIDE the lock (queries,
        inserts, and deletes keep flowing); (3) publish — one
        epoch-bumped view swap under the lock.  Inserts that raced phase
        2 stay in the (shrunk) delta; deletes that raced it land in the
        sealed tombstone array, so nothing is lost either way.
        """
        with self._mut_cond:  # acquires: compaction
            while self._compacting:
                if not wait:
                    return 0
                self._mut_cond.wait()
            view0 = self._view
            d0 = len(view0.delta)
            if d0 == 0:
                return 0
            self._compacting = True
        try:
            self._seal(view0, d0)
        finally:
            with self._mut_cond:  # acquires: compaction
                self._compacting = False
                self._mut_cond.notify_all()
        return d0

    def _seal(self, view0: IndexView, d0: int) -> None:
        """Phase 2+3 of :meth:`compact` — heavy work lock-free, publish
        atomic.  Only ever runs under the ``_compacting`` token, so
        ``view0``'s sealed tiers are still current at publish time (only
        compaction replaces them).

        Rows tombstoned at claim time are PURGED here, not carried: they
        get no PQ code, no posting membership, no SSD page (the ROADMAP
        streaming-index follow-on).  Global ids stay stable — the id
        space keeps counting purged rows — so the published view carries
        ``id_of``/``row_of`` maps between physical rows and ids; both are
        strictly increasing, which keeps candidate lists ascending and
        tie-breaks identical across compactions."""
        delta_vecs = view0.delta.vectors[:d0]
        snap_tomb = view0.delta.tombstoned[:d0]
        n_sealed = view0.n_sealed
        live_local = np.flatnonzero(~snap_tomb)
        n_live = len(live_local)
        live_vecs = delta_vecs[live_local]
        live_gids = (n_sealed + live_local).astype(np.int64)
        # DRAM tier: cluster the SURVIVORS against the EXISTING centroids
        # (deterministic — replicas stay in lockstep replaying the same
        # ops).  Posting members are physical ROW indices.
        members = list(view0.posting.members)
        primary = view0.posting.primary
        new_pl = None
        if n_live:
            new_pl = clustering.assign_with_replication(
                live_vecs, view0.posting.centroids,
                eps=self.cfg.replication_eps,
                max_replicas=self.cfg.max_replicas)
            for c in range(view0.posting.n_clusters):
                mem = new_pl.members[c]
                if len(mem):
                    members[c] = np.concatenate(
                        [members[c],
                         (mem + view0.n_rows).astype(np.int32)])
            primary = np.concatenate([primary, new_pl.primary])
        posting = clustering.PostingLists(
            centroids=view0.posting.centroids, members=members,
            primary=primary)
        # HBM tier: PQ-encode the survivors (rotated if OPQ) + append
        codes = view0.codes
        if n_live:
            enc_in = live_vecs
            if self.rotation is not None:
                enc_in = enc_in @ self.rotation
            new_codes = pq.encode(self.codebook, jnp.asarray(enc_in))
            codes = jnp.concatenate([view0.codes, new_codes], axis=0)
        # SSD tier: fresh pages bucketed by primary centroid (§4.3).
        # Prefix-preserving rebinds — rows a published view can name never
        # move, so readers of any older view stay consistent mid-seal.
        if n_live:
            lay = self.ssd.layout
            order = np.argsort(new_pl.primary, kind="stable")
            new_pages = lay.n_pages + np.arange(n_live) // lay.per_page
            page_of = np.empty(n_live, np.int64)
            page_of[order] = new_pages
            self.ssd.vectors = np.concatenate(
                [self.ssd.vectors,
                 live_vecs.astype(self.ssd.vectors.dtype)])
            lay.page_of = np.concatenate([lay.page_of, page_of])
            lay.n_pages = int(lay.page_of.max()) + 1
        id_of = np.concatenate([view0.id_of, live_gids])
        # publish: sealed tombstones take the PUBLISH-time delta flags —
        # a delete that raced the seal missed the purge above (its row IS
        # encoded), but the candidate-collection tombstone filter still
        # drops it.  Purged ids stay tombstoned-True in id space forever.
        # Attributes are id-space: ALL d0 rows carry over (harmless for
        # purged ids — the tombstone filter runs before any attr lookup).
        with self._mut_cond:  # acquires: compaction
            cur = self._view
            tomb = np.concatenate([cur.tombstones,
                                   cur.delta.tombstoned[:d0]])
            self._view = IndexView(
                epoch=cur.epoch + 1, codes=codes, posting=posting,
                tombstones=tomb, graph=cur.graph,
                delta=cur.delta.drop_prefix(d0),
                attrs=cur.attrs.extend(cur.delta.attrs.head(d0)),
                id_of=id_of)
            self._mut_cond.notify_all()

    def start_compactor(self, *, min_delta: int = 64,
                        poll_s: float = 0.05) -> SegmentCompactor:
        """Run background compaction off the pump thread: seals the delta
        whenever it reaches ``min_delta`` rows."""
        if self._compactor is None:
            self._compactor = SegmentCompactor(
                self, min_delta=min_delta, poll_s=poll_s).start()
        return self._compactor

    def stop_compactor(self, *, flush: bool = False) -> None:
        compactor = self._compactor
        if compactor is not None:
            self._compactor = None
            compactor.stop(flush=flush)

    # ------------------------------------------------------------- snapshots
    def save_snapshot(self, path: str) -> str:
        """Checkpoint every tier — PQ codes + codebooks, nav graph,
        posting lists, SSD layout + raw vectors, tombstones, and the live
        delta segment — to ``path/`` (manifest.json + arrays.npz).

        The view ref is pinned under the compaction lock; materialization
        and file I/O run outside it.  SSD arrays are truncated to the
        view's sealed prefix, so a compaction racing the save cannot leak
        rows the captured view does not publish.  A replica restored via
        :meth:`load_snapshot` answers queries with bit-identical ids.
        """
        with self._mut_cond:  # acquires: compaction
            view = self._view
        n_sealed = view.n_sealed
        n_rows = view.n_rows                  # physical rows (<= n_sealed)
        lay = self.ssd.layout
        page_of = np.asarray(lay.page_of[:n_rows], np.int64)
        arrays: Dict[str, np.ndarray] = {
            "codes": np.asarray(view.codes, np.uint8),
            "codebooks": np.asarray(self.codebook.codebooks, np.float32),
            "graph_points": view.graph.points,
            "graph_neighbors": view.graph.neighbors,
            "posting_centroids": view.posting.centroids,
            "posting_primary": view.posting.primary,
            "posting_members_flat": (
                np.concatenate(view.posting.members)
                if view.posting.n_clusters else np.zeros(0, np.int32)),
            "posting_offsets": np.cumsum(
                [0] + [len(m) for m in view.posting.members]).astype(np.int64),
            "tombstones": view.tombstones,
            "ssd_vectors": np.asarray(self.ssd.vectors[:n_rows]),
            "ssd_page_of": page_of,
            "id_of": view.id_of,
            "delta_vectors": view.delta.vectors,
            "delta_tombstoned": view.delta.tombstoned,
        }
        for name, col in view.attrs.columns.items():
            arrays[f"attr_sealed_{name}"] = col
        for name, col in view.delta.attrs.columns.items():
            arrays[f"attr_delta_{name}"] = col
        if self.rotation is not None:
            arrays["rotation"] = np.asarray(self.rotation, np.float32)
        if view.graph.super_centroids is not None:
            arrays["graph_super_centroids"] = view.graph.super_centroids
            arrays["graph_super_assign"] = view.graph.super_assign
        manifest = {
            "format_version": SNAPSHOT_FORMAT_VERSION,
            "epoch": int(view.epoch),
            "n_sealed": int(n_sealed),
            "n_rows": int(n_rows),
            "attr_sealed_cols": sorted(view.attrs.columns),
            "attr_delta_cols": sorted(view.delta.attrs.columns),
            "cfg": dataclasses.asdict(self.cfg),
            "graph_entry": int(view.graph.entry),
            "ssd": {
                "n_pages": int(page_of.max()) + 1 if n_rows else 0,
                "per_page": int(lay.per_page),
                "page_bytes": int(lay.page_bytes),
                "buffer_pages": int(self.ssd.buffer_pages),
                "intra_merge": bool(self.ssd.intra_merge),
                "use_buffer": bool(self.ssd.use_buffer),
            },
        }
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, _SNAPSHOT_MANIFEST), "w") as fh:
            json.dump(manifest, fh, indent=1)
        np.savez(os.path.join(path, _SNAPSHOT_ARRAYS), **arrays)
        return path

    @classmethod
    def load_snapshot(cls, path: str) -> "FusionANNSIndex":
        """Rebuild a full index — sealed tiers AND delta segment, at the
        saved epoch — from a :meth:`save_snapshot` directory.  This is how
        ``ReplicaRouter.add_replica`` hydrates a newcomer from disk
        instead of re-clustering/re-encoding from raw data."""
        with open(os.path.join(path, _SNAPSHOT_MANIFEST)) as fh:
            manifest = json.load(fh)
        if manifest["format_version"] not in _SNAPSHOT_COMPAT_VERSIONS:
            raise ValueError(
                f"snapshot format {manifest['format_version']} not in "
                f"{_SNAPSHOT_COMPAT_VERSIONS}")
        with np.load(os.path.join(path, _SNAPSHOT_ARRAYS)) as npz:
            arr = {k: npz[k] for k in npz.files}
        cfg = ANNSConfig(**manifest["cfg"])
        offsets = arr["posting_offsets"]
        flat = arr["posting_members_flat"]
        posting = clustering.PostingLists(
            centroids=arr["posting_centroids"],
            members=[flat[offsets[i]:offsets[i + 1]]
                     for i in range(len(offsets) - 1)],
            primary=arr["posting_primary"])
        graph = ng.NavGraph(
            points=arr["graph_points"], neighbors=arr["graph_neighbors"],
            entry=manifest["graph_entry"],
            super_centroids=arr.get("graph_super_centroids"),
            super_assign=arr.get("graph_super_assign"))
        ssd_meta = manifest["ssd"]
        layout = StorageLayout(
            page_of=arr["ssd_page_of"], n_pages=ssd_meta["n_pages"],
            per_page=ssd_meta["per_page"], page_bytes=ssd_meta["page_bytes"])
        ssd = SSDSim(arr["ssd_vectors"], layout,
                     buffer_pages=ssd_meta["buffer_pages"],
                     intra_merge=ssd_meta["intra_merge"],
                     use_buffer=ssd_meta["use_buffer"])
        codes = jnp.asarray(arr["codes"])
        # v1 snapshots carry no id map / attributes: identity + empty
        id_of = arr.get("id_of")
        n_sealed = int(manifest["n_sealed"])
        sealed_attrs = AttributeTable.from_columns(
            n_sealed, {name: arr[f"attr_sealed_{name}"]
                       for name in manifest.get("attr_sealed_cols", [])})
        delta_attrs = AttributeTable.from_columns(
            len(arr["delta_vectors"]),
            {name: arr[f"attr_delta_{name}"]
             for name in manifest.get("attr_delta_cols", [])})
        index = cls(cfg=cfg, codebook=pq.PQCodebook(
                        codebooks=jnp.asarray(arr["codebooks"])),
                    codes=codes, posting=posting, graph=graph, ssd=ssd,
                    rotation=arr.get("rotation"),
                    tombstones=arr["tombstones"], id_of=id_of)
        # restore the delta + epoch too: a hydrated replica must answer
        # bit-identically to the donor, including its unsealed tail
        index._view = IndexView(
            epoch=manifest["epoch"], codes=codes, posting=posting,
            tombstones=np.asarray(arr["tombstones"], bool), graph=graph,
            delta=DeltaSegment(base=n_sealed,
                               vectors=arr["delta_vectors"],
                               tombstoned=np.asarray(
                                   arr["delta_tombstoned"], bool),
                               attrs=delta_attrs),
            attrs=sealed_attrs, id_of=id_of)
        return index

    # ------------------------------------------------------------------ query
    def candidate_ids(self, query: np.ndarray, top_m: int,
                      dedup: bool = True) -> np.ndarray:
        """Stages ②③⑤ against the current view's sealed segments."""
        return self._view.candidate_ids(query, top_m, dedup)

    @property
    def executor(self) -> QueryExecutor:
        """The unified QueryPlan -> QueryExecutor pipeline (core.executor).
        Shared by all three public query paths; call
        ``.executor.attach_mesh(mesh)`` to row-shard the HBM tier."""
        ex = getattr(self, "_executor", None)
        if ex is None:
            ex = QueryExecutor(self)
            self._executor = ex
        return ex

    def make_executor(self, mesh=None) -> QueryExecutor:
        """A FRESH executor over this index (multi-replica serving: each
        replica owns its own executor, optionally attached to a disjoint
        sub-mesh from ``launch.mesh.split_mesh``).  All executors share
        the index's published view — an executor pins ``index.view()``
        per scan window, so every insert/delete/compaction epoch reaches
        every replica at its next dispatch."""
        return QueryExecutor(self, mesh=mesh)

    def plan(self, *, k: Optional[int] = None, top_m: Optional[int] = None,
             top_n: Optional[int] = None, **kw) -> QueryPlan:
        return QueryPlan.from_config(self.cfg, k=k, top_m=top_m,
                                     top_n=top_n, **kw)

    def submit(self, queries: np.ndarray, *, k: Optional[int] = None,
               top_m: Optional[int] = None, top_n: Optional[int] = None,
               overrides: Optional[List[Optional[PlanOverrides]]] = None,
               **kw) -> BatchTicket:
        """Futures-first entry point (DESIGN.md §3): host traversal + async
        device dispatch, then return immediately.  ``kw`` passes plan knobs
        through (``window=``, ``inflight_depth=``, ``deadline_s=``, ...);
        ``overrides`` carries per-query ``PlanOverrides`` for mixed-``k``
        windows."""
        return self.executor.submit(
            queries, self.plan(k=k, top_m=top_m, top_n=top_n, **kw),
            overrides=overrides)

    def search(self, request):
        """Typed single-request serve (DESIGN.md §6): accepts a
        :class:`~repro.serve.client.SearchRequest` and returns its
        :class:`~repro.serve.client.SearchResponse` through the shared
        executor's Backend-protocol path — same ids as :meth:`query`."""
        return self.executor.submit(request).result()

    def query(self, query: np.ndarray, *, k: Optional[int] = None,
              top_m: Optional[int] = None, top_n: Optional[int] = None,
              disable_early_stop: bool = False) -> QueryResult:
        """Single query == a window of one through the unified executor."""
        return self.executor.run_one(query, self.plan(
            k=k, top_m=top_m, top_n=top_n,
            disable_early_stop=disable_early_stop))

    def batch_query(self, queries: np.ndarray, *, k: Optional[int] = None,
                    top_m: Optional[int] = None, top_n: Optional[int] = None,
                    disable_early_stop: bool = False) -> List[QueryResult]:
        """Per-query windows (window=1): no inter-query candidate sharing."""
        return self.executor.run(queries, self.plan(
            k=k, top_m=top_m, top_n=top_n,
            disable_early_stop=disable_early_stop, window=1))

    def query_batch_fused(self, queries: np.ndarray, *,
                          k: Optional[int] = None,
                          top_m: Optional[int] = None,
                          top_n: Optional[int] = None) -> List[QueryResult]:
        """Beyond-paper batched mode (the TPU adaptation's natural shape):
        one ADC scan over the UNION of the batch's candidate ids with all B
        LUTs resident, per-query masking + top-n — inter-query dedup is the
        paper's §4.3 redundancy insight applied to the HBM scan.  One window
        through the unified executor; identical per-query semantics."""
        return self.executor.run(queries, self.plan(
            k=k, top_m=top_m, top_n=top_n))


# ---------------------------------------------------------------------------
# Evaluation helpers
# ---------------------------------------------------------------------------

def ground_truth(data: np.ndarray, queries: np.ndarray, k: int,
                 chunk: int = 4096) -> np.ndarray:
    """Exact top-k ids per query (brute force, chunked)."""
    q = queries.astype(np.float32)
    out = np.empty((len(q), k), np.int64)
    d2_best = None
    for qi in range(0, len(q), 128):
        qb = q[qi:qi + 128]
        d2 = np.empty((len(qb), len(data)), np.float32)
        for s in range(0, len(data), chunk):
            blk = data[s:s + chunk].astype(np.float32)
            d2[:, s:s + chunk] = (np.sum(qb ** 2, -1)[:, None]
                                  - 2.0 * qb @ blk.T
                                  + np.sum(blk ** 2, -1)[None])
        idx = np.argpartition(d2, k - 1, axis=1)[:, :k]
        dd = np.take_along_axis(d2, idx, axis=1)
        out[qi:qi + len(qb)] = np.take_along_axis(
            idx, np.argsort(dd, axis=1), axis=1)
    return out


def recall_at_k(result_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    """Recall@k — |result ∩ gt| / k, averaged over queries."""
    hits = 0
    for r, g in zip(np.atleast_2d(result_ids), np.atleast_2d(gt_ids)):
        hits += len(set(r[:k].tolist()) & set(g[:k].tolist()))
    return hits / (len(np.atleast_2d(gt_ids)) * k)
