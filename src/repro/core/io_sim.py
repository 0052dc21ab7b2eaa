"""SSD tier simulation with exact 4 KB-page semantics (paper §4.3).

Implements the optimised storage layout (per-centroid buckets,
first-fit-decreasing remainder bin-packing so partial pages are shared),
the vec->page mapping table, Direct-I/O page reads, and the two dedup
mechanisms:

  * intra-mini-batch: requests hitting the same page within ONE ``fetch()``
    are merged,
  * inter-mini-batch: a (per-query) DRAM page buffer absorbs repeats
    ACROSS ``fetch()`` calls.

The two mechanisms are strictly separated for the Fig. 12 per-mechanism
attribution: the page buffer only serves pages read by *previous*
mini-batches, so disabling ``intra_merge`` really does charge one I/O per
same-page request inside a batch (insertions into the buffer are deferred
to the end of the fetch).  Every mechanism can be disabled independently.
I/O counts and byte volumes are exact; latency is modelled by the analytic
device model in ``core.baselines`` (no NVMe in this container — DESIGN.md §7).

Two forms give the same counts.  ``fetch`` accounts one mini-batch at a
time against the buffer of the current query scope (``begin_query``), one
query at a time.  ``account`` counts a whole group of finished re-ranks at
once from each query's rows and mini-batch count, and keeps no state, so
concurrent re-ranks on the serving path share one ``SSDSim``: where a
query touches no more distinct pages than the buffer holds, the LRU never
evicts and each page costs one I/O in the mini-batch where it first
appears; otherwise it replays ``fetch``'s walk with a buffer of its own.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class IOStats:
    ios: int = 0                 # page reads issued to the "SSD"
    pages_requested: int = 0     # before any dedup
    buffer_hits: int = 0         # inter-mini-batch dedup (DRAM page buffer)
    intra_merged: int = 0        # intra-mini-batch dedup (same-page merge)
    bytes_read: int = 0

    def merge(self, other: "IOStats") -> "IOStats":
        return IOStats(self.ios + other.ios,
                       self.pages_requested + other.pages_requested,
                       self.buffer_hits + other.buffer_hits,
                       self.intra_merged + other.intra_merged,
                       self.bytes_read + other.bytes_read)


class PageBuffer:
    """LRU DRAM page buffer (inter-mini-batch dedup)."""

    def __init__(self, capacity_pages: int):
        self.capacity = capacity_pages
        self._lru: "OrderedDict[int, bool]" = OrderedDict()

    def hit(self, page: int) -> bool:
        if page in self._lru:
            self._lru.move_to_end(page)
            return True
        return False

    def insert(self, page: int) -> None:
        self._lru[page] = True
        self._lru.move_to_end(page)
        while len(self._lru) > self.capacity:
            self._lru.popitem(last=False)

    def clear(self) -> None:
        self._lru.clear()


def pack_buckets_maxmin(bucket_sizes: Sequence[int], per_page: int
                        ) -> Tuple[List[List[int]], int]:
    """First-fit-decreasing packing of bucket *remainders* into shared
    pages (§4.3's shared-page layout).

    Full pages are dedicated; remainders are sorted descending and each is
    placed into the FIRST open page with room (first-fit-decreasing — not
    the max-min pairing the name suggests; the name is kept for API
    stability).  Returns (groups of bucket-ids sharing a page, total pages
    used)."""
    full_pages = sum(s // per_page for s in bucket_sizes)
    rema = [(s % per_page, i) for i, s in enumerate(bucket_sizes)
            if s % per_page]
    rema.sort(reverse=True)
    groups: List[List[int]] = []
    loads: List[int] = []
    for size, bid in rema:
        placed = False
        for gi in range(len(groups)):
            if loads[gi] + size <= per_page:
                groups[gi].append(bid)
                loads[gi] += size
                placed = True
                break
        if not placed:
            groups.append([bid])
            loads.append(size)
    return groups, full_pages + len(groups)


@dataclasses.dataclass
class StorageLayout:
    """vec_id -> page mapping under the optimised bucket layout."""

    page_of: np.ndarray            # (N,) int64 page id per vector
    n_pages: int
    per_page: int
    page_bytes: int

    @staticmethod
    def build(primary_cluster: np.ndarray, n_clusters: int,
              vec_bytes: int, page_bytes: int = 4096,
              optimized: bool = True) -> "StorageLayout":
        """``primary_cluster[v]`` = the single bucket that stores v (no
        duplicates across buckets — paper §4.3).  ``optimized=False`` lays
        vectors out in insertion order (the straw-man layout)."""
        n = len(primary_cluster)
        per_page = max(1, page_bytes // vec_bytes)
        page_of = np.empty(n, np.int64)
        if not optimized:
            page_of[:] = np.arange(n) // per_page
            return StorageLayout(page_of, int(page_of.max()) + 1 if n else 0,
                                 per_page, page_bytes)
        # group vectors by bucket; remainders share pages via max-min
        order = np.argsort(primary_cluster, kind="stable")
        sizes = np.bincount(primary_cluster, minlength=n_clusters)
        groups, n_pages = pack_buckets_maxmin(sizes.tolist(), per_page)
        # assign pages: first the full pages bucket-by-bucket, then groups
        page = 0
        starts = np.zeros(n_clusters + 1, np.int64)
        np.cumsum(sizes, out=starts[1:])
        slot_page = np.empty(n, np.int64)   # page of the i-th sorted vector
        rem_start: Dict[int, int] = {}
        for c in range(n_clusters):
            full = sizes[c] // per_page
            for f in range(full):
                s = starts[c] + f * per_page
                slot_page[s:s + per_page] = page
                page += 1
            rem_start[c] = starts[c] + full * per_page
        for grp in groups:
            for bid in grp:
                s = rem_start[bid]
                e = starts[bid] + sizes[bid]
                slot_page[s:e] = page
            page += 1
        page_of[order] = slot_page
        return StorageLayout(page_of, page, per_page, page_bytes)


class SSDSim:
    """Raw-vector store with page-granular reads + dedup mechanisms."""

    def __init__(self, vectors: np.ndarray, layout: StorageLayout,
                 buffer_pages: int = 1024, *,
                 intra_merge: bool = True, use_buffer: bool = True):
        self.vectors = vectors
        self.layout = layout
        self.intra_merge = intra_merge
        self.use_buffer = use_buffer
        self.buffer_pages = buffer_pages
        self.buffer = PageBuffer(buffer_pages)   # ``fetch``'s query scope

    def begin_query(self) -> IOStats:
        """Per-query buffer scope (the paper's DRAM buffer is per-query
        working memory)."""
        self.buffer.clear()
        return IOStats()

    def fetch(self, vec_ids: np.ndarray, stats: IOStats) -> np.ndarray:
        """One re-ranking mini-batch: returns the raw vectors, accounting
        page I/O with intra-batch merge + buffer dedup.

        Buffer insertions are deferred until the whole mini-batch is
        accounted: the buffer is the INTER-mini-batch mechanism, so with
        ``intra_merge=False`` same-page requests inside one batch each
        cost an I/O instead of being silently absorbed by the buffer
        (keeps the Fig. 12 per-mechanism attribution honest)."""
        self._account_batch(self.layout.page_of[vec_ids], stats, self.buffer)
        return self.vectors[vec_ids]

    def _account_batch(self, pages: np.ndarray, stats: IOStats,
                       buf: PageBuffer) -> None:
        """One mini-batch's page reads against ``buf``: ``fetch``'s rule."""
        stats.pages_requested += len(pages)
        wanted = pages if not self.intra_merge else np.unique(pages)
        # per-mechanism attribution invariant (Fig. 12):
        #   pages_requested - ios == intra_merged + buffer_hits
        stats.intra_merged += len(pages) - len(wanted)
        read_this_batch: List[int] = []       # read order (dups included)
        for p in wanted:
            p = int(p)
            if self.use_buffer and buf.hit(p):
                stats.buffer_hits += 1
                continue
            stats.ios += 1
            stats.bytes_read += self.layout.page_bytes
            read_this_batch.append(p)
        if self.use_buffer:
            # sequential inserts in read order: LRU recency matches the
            # actual read sequence (a repeat moves its page to the tail)
            for p in read_this_batch:
                buf.insert(p)

    def account(self, rows: Sequence[np.ndarray], batches_run: Sequence[int],
                batch_size: int) -> List[IOStats]:
        """Page I/O of finished re-ranks: query i read the first
        ``batches_run[i]`` mini-batches of ``batch_size`` of ``rows[i]``.
        Returns each query's counts, equal to what ``begin_query`` and one
        ``fetch`` per mini-batch give."""
        lens = [min(len(r), int(b) * batch_size)
                for r, b in zip(rows, batches_run)]
        out = [IOStats() for _ in lens]
        replay = range(len(lens))
        if self.intra_merge and self.use_buffer and sum(lens):
            # one sort of (query, page, mini-batch) keys counts the
            # distinct pages of each query and of each of its mini-batches
            n_mb = -(-max(lens) // batch_size)
            span = self.layout.n_pages * n_mb
            offset, size = [], []
            for i, n in enumerate(lens):
                offset += [i * span + m for m in range(-(-n // batch_size))]
                size += [batch_size] * (n // batch_size)
                if n % batch_size:
                    size.append(n % batch_size)
            key = self.layout.page_of[np.concatenate(
                [r[:n] for r, n in zip(rows, lens)])] * n_mb
            key += np.repeat(offset, size)
            key.sort()
            page = key // n_mb
            query = page // self.layout.n_pages
            # the first key of each query opens a new page and mini-batch
            per_mb = np.bincount(query[1:], key[1:] != key[:-1], len(lens))
            distinct = np.bincount(query[1:], page[1:] != page[:-1],
                                   len(lens))
            per_mb[query[0]] += 1
            distinct[query[0]] += 1
            replay = []
            for i, n in enumerate(lens):
                # fits in the buffer: the LRU never evicts, so a page costs
                # one I/O where it first appears and a hit in each later
                # mini-batch that asks for it
                if distinct[i] > self.buffer_pages:
                    replay.append(i)
                    continue
                st = out[i]
                st.pages_requested = n
                st.ios = int(distinct[i])
                st.intra_merged = n - int(per_mb[i])
                st.buffer_hits = int(per_mb[i] - distinct[i])
                st.bytes_read = st.ios * self.layout.page_bytes
        for i in replay:
            buf = PageBuffer(self.buffer_pages)
            for b0 in range(0, lens[i], batch_size):
                self._account_batch(
                    self.layout.page_of[rows[i][b0:b0 + batch_size]],
                    out[i], buf)
        return out


@dataclasses.dataclass
class PostingListStore:
    """SPANN-style layout: whole posting lists stored contiguously on SSD;
    a query reads each selected list in full (multi-page I/Os)."""

    list_pages: np.ndarray        # pages per posting list
    page_bytes: int = 4096

    @staticmethod
    def build(member_counts: Sequence[int], entry_bytes: int,
              page_bytes: int = 4096) -> "PostingListStore":
        pages = np.array([max(1, int(np.ceil(c * entry_bytes / page_bytes)))
                          for c in member_counts], np.int64)
        return PostingListStore(pages, page_bytes)

    def read_lists(self, list_ids: np.ndarray, stats: IOStats) -> None:
        # one I/O per list (SPANN issues large sequential reads), but the
        # byte volume spans all its pages
        pages = self.list_pages[list_ids]
        stats.ios += len(list_ids)
        stats.pages_requested += int(pages.sum())
        stats.bytes_read += int(pages.sum()) * self.page_bytes
