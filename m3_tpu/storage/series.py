"""Series registry: id <-> dense index mapping per shard.

The reference's dbShard keeps a concurrent map id -> *dbSeries with each
series owning encoders and cached blocks (storage/shard.go, generated
shard_map_gen.go). In the columnar design, per-series state collapses to a
dense int32 index used across buffer columns and block rows; the registry
is the only id-keyed structure on the hot path."""

from __future__ import annotations

import threading
from itertools import repeat
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# `_indexed` of a series the reverse index holds in no block yet.
NEVER_INDEXED = -(1 << 62)


class SeriesRegistry:
    def __init__(self):
        self._index: Dict[bytes, int] = {}
        self._ids: List[bytes] = []
        self._tags: List[Optional[dict]] = []
        # Per series, the start of the newest reverse-index block that
        # holds its document (the reference's entry.IndexedForBlockStart):
        # a series is indexed again in every index block it is written
        # in, so a query that overlaps only a later block still finds
        # it. Capacity doubles; entries past len(_ids) are NEVER_INDEXED.
        # `index_floor` is a lower bound of the live entries, so "is any
        # series of this shard behind block B" is one integer test on
        # the write path; it moves under _untagged_lock. A series
        # without tags has no document and stays NEVER_INDEXED above the
        # bound until a write brings its tags (ensure_tags).
        self._indexed = np.full(1024, NEVER_INDEXED, np.int64)
        self.index_floor = NEVER_INDEXED
        # How many entries of _tags are None, so "does this batch hold a
        # series to backfill" is one integer test on the write path. It
        # moves under _untagged_lock (a leaf), up BEFORE the series' id
        # is published and down AFTER its tags are stored: a lock-free
        # reader that sees 0 can resolve no untagged series.
        self.untagged = 0
        self._untagged_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._ids)

    def get(self, series_id: bytes) -> Optional[int]:
        return self._index.get(series_id)

    def id_of(self, idx: int) -> bytes:
        return self._ids[idx]

    def tags_of(self, idx: int) -> Optional[dict]:
        return self._tags[idx]

    def get_or_create(self, series_id: bytes, tags: Optional[dict] = None) -> Tuple[int, bool]:
        idx = self._index.get(series_id)
        if idx is not None:
            self.ensure_tags(idx, tags)
            return idx, False
        idx = len(self._ids)
        self._grow_indexed(1)
        # Lists BEFORE the id map: lock-free readers (lookup_batch, the
        # write fast path) resolve through _index and then read
        # _ids/_tags without the shard lock — an index published first
        # would briefly point past the lists.
        self._ids.append(series_id)
        self._tags.append(tags)
        if tags is None:
            self._count_untagged(1)
        self._index[series_id] = idx
        return idx, True

    def get_or_create_batch(self, ids: Sequence[bytes]) -> Tuple[np.ndarray, List[int]]:
        """Bulk resolve; returns (indices [N], list of newly created idxs)."""
        out, created = self.get_or_create_batch_tagged(ids, None)
        return out, [int(out[j]) for j in created]

    def get_or_create_batch_tagged(
            self, ids: Sequence[bytes],
            tags: Optional[Sequence[Optional[dict]]],
    ) -> Tuple[np.ndarray, List[int]]:
        """Bulk resolve with tags; returns (indices [N], positions in
        `ids` that created a NEW series). This is the insert-queue
        drain's registry cost, paid once per coalesced batch under the
        shard lock (shard_insert_queue.go insertSeriesBatch).

        Queued ids were unknown at enqueue time, so the all-new case is
        the common one: probe it with one C-level membership pass and
        commit with dict.update(zip(...)) instead of a Python-level
        per-id loop; races and duplicate enqueues fall back to the
        general loop."""
        n = len(ids)
        index = self._index
        id_list = self._ids
        tag_list = self._tags
        base = len(id_list)
        self._grow_indexed(n)
        if not any(map(index.__contains__, ids)) and \
                len(dict.fromkeys(ids)) == n:
            out = np.arange(base, base + n, dtype=np.int32)
            # Lists BEFORE the id map (see get_or_create): lock-free
            # readers must never resolve an index past the lists' ends.
            id_list.extend(ids)
            tag_list.extend(tags if tags is not None else (None,) * n)
            self._count_untagged(
                n if tags is None else sum(t is None for t in tags))
            index.update(zip(ids, range(base, base + n)))
            return out, list(range(n))
        out = np.empty(n, np.int32)
        created: List[int] = []
        get = index.get
        for i, sid in enumerate(ids):
            t = tags[i] if tags is not None else None
            idx = get(sid)
            if idx is None:
                idx = len(id_list)
                id_list.append(sid)
                tag_list.append(t)
                if t is None:
                    self._count_untagged(1)
                index[sid] = idx
                created.append(i)
            else:
                self.ensure_tags(idx, t)
            out[i] = idx
        return out, created

    def lookup_batch(self, ids: Sequence[bytes]) -> np.ndarray:
        """Lock-free bulk resolve against a registry snapshot (-1 for
        unknown ids). Safe without the shard lock: the id->index map is
        append-only and an index, once assigned, is never reassigned —
        a concurrent insert can only turn a miss into a hit for later
        reads, never corrupt a resolved index. This is the write path's
        fast-path resolve (the lock-free read the reference gets from
        its concurrent shard map, shard.go lookupEntryWithLock's RLock
        fast path)."""
        # map(get, ids, repeat(-1)) iterates at C speed — no Python frame
        # per id, unlike a generator expression.
        return np.fromiter(map(self._index.get, ids, repeat(-1)), np.int32,
                           count=len(ids))

    def lookup_known(self, ids: Sequence[bytes]) -> Optional[np.ndarray]:
        """`lookup_batch` where every id is known, else None: the write
        path's common answer and its test for unknowns in one pass (a
        miss raises out of the C-level iteration). Lock-free on the
        same terms as lookup_batch."""
        try:
            return np.fromiter(map(self._index.__getitem__, ids), np.int32,
                               count=len(ids))
        except KeyError:
            return None

    def ensure_tags(self, idx: int, tags: Optional[dict]) -> bool:
        """Backfill tags for an existing series; True where this call
        stored them (racing writers carry equivalent tags for the same
        id: one of them stores, and the untagged count moves once)."""
        if tags is None or self._tags[idx] is not None:
            return False
        with self._untagged_lock:
            if self._tags[idx] is not None:
                return False
            self._tags[idx] = tags
            self.untagged -= 1
            # It had no document to index; its next write indexes it.
            self._indexed[idx] = NEVER_INDEXED
            self.index_floor = NEVER_INDEXED
        return True

    def ensure_tags_batch(self, sidx: Sequence[int],
                          tags: Sequence[Optional[dict]]) -> int:
        """`ensure_tags` for a batch's rows (`sidx[i] < 0`: not a known
        series, skipped); returns how many it backfilled. Call it where
        `untagged` is non-zero: rows of series that hold their tags
        cost one list probe each and no call."""
        held = self._tags
        return sum(self.ensure_tags(x, tags[i])
                   for i, x in enumerate(sidx)
                   if x >= 0 and held[x] is None)

    def _count_untagged(self, n: int):
        if n:
            with self._untagged_lock:
                self.untagged += n

    # ---------------------------------------------------- reverse-index marks

    def _grow_indexed(self, need: int):
        """Room for `need` more series, before their ids are published
        (under the shard lock, like every creation). A writer that
        marked the array this replaces loses its mark and indexes the
        series once more: the index takes a document twice."""
        have = len(self._ids) + need
        if have > len(self._indexed):
            grown = np.full(max(2 * len(self._indexed), have),
                            NEVER_INDEXED, np.int64)
            with self._untagged_lock:
                grown[:len(self._indexed)] = self._indexed
                self._indexed = grown

    def behind(self, sidx: np.ndarray, index_block: int) -> np.ndarray:
        """Positions in `sidx` of series the index does not hold in
        `index_block` or a later block. Lock-free."""
        return np.flatnonzero(self._indexed[sidx] < index_block)

    def mark_indexed(self, idxs, index_block: int):
        """The index holds these series' documents in `index_block`
        (a new series' first block may lie under the bound)."""
        with self._untagged_lock:
            held = self._indexed
            held[idxs] = np.maximum(held[idxs], index_block)
            if index_block < self.index_floor:
                self.index_floor = index_block

    def raise_index_floor(self):
        """Recompute the bound once a batch found nobody behind: the
        least mark among the series that have one."""
        with self._untagged_lock:
            marks = self._indexed[:len(self._ids)]
            marks = marks[marks > NEVER_INDEXED]
            self.index_floor = int(marks.min()) if len(marks) \
                else NEVER_INDEXED

    def all_ids(self) -> List[bytes]:
        return list(self._ids)

    def identities(self, idxs: Sequence[int]
                   ) -> Tuple[List[Optional[dict]], int]:
        """A sweep of indices' tags and the approximate wire bytes of
        serving their identities (ids + tag pairs) — the floor a tagged
        fetch pays for them before any datapoint bytes. Feeds the
        bytes-read query limit (the registry is the only id-keyed
        structure on the hot path, so identity-cost accounting lives
        here with it). No Python frame a series but the tag walk."""
        tags = list(map(self._tags.__getitem__, idxs))
        n = sum(map(len, map(self._ids.__getitem__, idxs)))
        for t in tags:
            if t:
                n += sum(map(len, t)) + sum(map(len, t.values()))
        return tags, n


def charge_read(n_series: int = 0, n_points: int = 0, n_bytes: int = 0):
    """Charge a storage read against the query limits registry
    (utils.limits): series materialized, datapoints decoded, encoded
    bytes touched. One helper so every read path (database.read,
    query_ids, the node fetch fan-ins) meters identically; raises
    ResourceExhausted past a budget."""
    from ..utils import limits as xlimits

    if n_series:
        xlimits.charge("series_fetched", n_series)
    if n_points:
        xlimits.charge("datapoints_decoded", n_points)
    if n_bytes:
        xlimits.charge("bytes_read", n_bytes)


# Runtime race witness registration (utils/racewatch.py): the registry's
# lock-free append-before-publish protocol is DECLARED in
# analysis/lockfree_ledger.txt, so its attrs stay instrumented — the
# declaration is verified dynamically, never silently trusted.
from ..utils import racewatch as _racewatch  # noqa: E402

_racewatch.register(SeriesRegistry, "_index", "_ids", "_tags")
