"""Shard sets: murmur3 virtual-shard hashing (reference:
src/dbnode/sharding/shardset.go — murmur3.Sum32(id) % numShards over 4096
default virtual shards, docs/m3db/architecture/sharding.md)."""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..utils.hashing import hash_batch, hash_batch_host, murmur3_32
from ..utils.instrument import ROOT

DEFAULT_NUM_SHARDS = 4096

# The shard memo's bounds, both dimensions (as utils.hashing's murmur
# memo): ids come from clients before any validation.
MEMO_MAX_ENTRIES = 65536
MEMO_MAX_KEY = 256

_memo_scope = ROOT.sub_scope("sharding.memo")
_MEMO_HITS = _memo_scope.counter("hits")
_MEMO_MISSES = _memo_scope.counter("misses")


class ShardSet:
    """The set of virtual shards this node (or a topology) hashes over."""

    def __init__(self, num_shards: int = DEFAULT_NUM_SHARDS,
                 owned: Optional[Sequence[int]] = None):
        self.num_shards = num_shards
        self.owned = sorted(owned) if owned is not None else list(range(num_shards))
        # id -> shard of series a serving path has routed before
        # (lookup_memo). Probes are lock-free dict reads; the lock
        # orders the miss path's bound check with its insert.
        self._memo: Dict[bytes, int] = {}
        self._memo_lock = threading.Lock()

    def lookup(self, series_id: bytes) -> int:
        """shardset.go:76 Lookup."""
        return murmur3_32(series_id) % self.num_shards

    def lookup_batch(self, ids: Sequence[bytes]) -> np.ndarray:
        return (hash_batch(ids) % np.uint32(self.num_shards)).astype(np.int32)

    def lookup_memo(self, ids: Sequence[bytes]) -> np.ndarray:
        """`lookup_batch` for a request's rows: a series' shard is a pure
        function of its id and the same series come back every scrape,
        so a known id is one dict probe and the request path makes no
        codec dispatch and compiles nothing. First sightings hash on the
        host (hash_batch_host), whatever their number, and are
        remembered: at most MEMO_MAX_ENTRIES ids of at most MEMO_MAX_KEY
        bytes, flushed whole when full. Counts `sharding.memo.hits` and
        `.misses`. Bulk loads keep lookup_batch."""
        found = list(map(self._memo.get, ids))
        missed = found.count(None)
        if missed:
            new_ids = list(dict.fromkeys(
                sid for sid, s in zip(ids, found) if s is None))
            fresh = dict(zip(new_ids, (hash_batch_host(new_ids)
                                       % np.uint32(self.num_shards)).tolist()))
            found = [fresh[sid] if s is None else s
                     for sid, s in zip(ids, found)]
            keep = {sid: s for sid, s in fresh.items()
                    if len(sid) <= MEMO_MAX_KEY}
            if len(keep) <= MEMO_MAX_ENTRIES:  # else: a bulk load, passing
                with self._memo_lock:
                    if len(self._memo) + len(keep) > MEMO_MAX_ENTRIES:
                        self._memo.clear()
                    self._memo.update(keep)
            _MEMO_MISSES.inc(missed)
        if missed < len(found):
            _MEMO_HITS.inc(len(found) - missed)
        return np.array(found, np.int32)

    def all_shard_ids(self) -> List[int]:
        return list(self.owned)

    def owns(self, shard_id: int) -> bool:
        return shard_id in set(self.owned)
