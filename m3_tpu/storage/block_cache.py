"""Device-memory hot tier for read serving (reference: the dbnode block
retriever's series cache policies — src/dbnode/storage/series/policy.go
CacheAll / CacheRecentlyRead / CacheLRU — and the byte-bounded WiredList of
block/wired_list.go:77 that keeps hot blocks decodable without disk).

The TPU twist: sealed blocks are ENCODED ON DEVICE by the mesh flush
(parallel/ingest.flush_encode_prepared), then today shipped to the host and
the device buffers discarded — only for the next query to re-upload the
same bytes. `DeviceBlockCache` closes that loop:

  (a) retain — at seal/flush time the shard hands the just-encoded device
      arrays (words [S, MW] u32 + padded npoints) to the cache instead of
      dropping them after the host transfer, so the block stays decodable
      on its mesh devices with zero H2D traffic (producer output sharding
      == consumer input sharding, the pjit guidance of SNIPPETS [1]).
  (b) serve — `SealedBlock.read`/`read_all` consult the cache before any
      decode: a hit returns the block's decoded (ts, vals) planes (frozen
      arrays, shared across readers); a miss on a HOT block (admission:
      `admit_after` touches per generation, the RecentlyRead policy's
      "promote on re-read") decodes the whole block ONCE — from the
      retained device buffers when present — and caches the planes. A
      fetch's batched read (storage/read_batch.py) never pays for more
      than one such decode: while the budget has room the blocks it
      read cold go to the cache's fill thread (`offer`; the reference's
      block retriever fetches beside its requests too).
  (c) bound — residency is charged to the process-wide `HBMBudget`
      (utils/hbm.py) shared with the selector-grid upload caches, evicted
      LRU under one global ceiling, and invalidated through the same
      seal / merge / expiry drop hooks the postings-list cache uses
      (index/postings_cache.py): every hook that replaces or drops a
      SealedBlock invalidates its generation, and put()s for dead
      generations are refused so a query racing a seal can never re-pin a
      dropped block's arrays (the PR 3 postings-cache hazard).

Keys are block GENERATIONS: every SealedBlock construction gets a
process-unique `gen` (storage/block.py), so a merge/re-seal/bootstrap
replacement produces a new key by construction and the old entries are
unreachable even before the eager invalidation lands. Entry metadata
carries (namespace, shard, block_start) for observability.

Counters (hits/misses/evictions/invalidations/admitted/retained) export in
instrument scope `storage.block_cache`; bytes ride the shared budget's
gauges, and budget pressure is the HealthTracker memory-pressure probe.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from collections import OrderedDict, deque
from typing import Dict, Optional, Tuple

import numpy as np

from ..parallel import scope as dscope
from ..utils import foreground, instrument, tracing
from ..utils.hbm import HBMBudget, shared_budget

__all__ = ["DeviceBlockCache", "get_cache", "active", "disabled"]

# Generations a query may still try to (re)populate after their block was
# dropped; bounded like the postings cache's dead-gen memory.
_DEAD_GENS_MAX = 4096
# Touch counters for not-yet-admitted generations (bounded; cold blocks
# cycling through fall off the end and simply restart their count).
_TOUCH_MAX = 8192

DEFAULT_ADMIT_AFTER = 2
# utils/tracing.py counts the fill thread's CPU under this name's role.
FILL_THREAD_NAME = "block-cache-fill"
# How long the fill thread waits for a moment with no request in service
# before each block (utils/foreground.py): a server that is never quiet
# still fills, at some 15 blocks a second.
FILL_STANDS_BACK_S = 0.05

_LOG = logging.getLogger(__name__)


def plane_bytes(blk) -> int:
    """What a block's decoded planes hold: int64 + float64 a cell."""
    return int(blk.num_series) * int(blk.window) * 16


class _Entry:
    __slots__ = ("decoded", "encoded", "nbytes", "meta")

    def __init__(self):
        self.decoded: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.encoded: Optional[tuple] = None
        self.nbytes = 0
        self.meta: Optional[Tuple[bytes, int, int]] = None


class DeviceBlockCache:
    """LRU-with-admission over sealed blocks' device buffers and decoded
    planes, keyed by block generation, bounded by the shared HBM budget."""

    def __init__(self, budget: Optional[HBMBudget] = None,
                 admit_after: Optional[int] = None,
                 scope: Optional[instrument.Scope] = None,
                 tenant: str = "block_cache"):
        self.budget = budget if budget is not None else shared_budget()
        self.admit_after = admit_after if admit_after is not None else int(
            os.environ.get("M3_TPU_BLOCK_CACHE_ADMIT",
                           str(DEFAULT_ADMIT_AFTER)))
        self.enabled = os.environ.get("M3_TPU_BLOCK_CACHE", "1") != "0"
        self._lock = threading.Lock()
        self._entries: "OrderedDict[int, _Entry]" = OrderedDict()
        self._touch: "OrderedDict[int, int]" = OrderedDict()
        self._dead: "OrderedDict[int, None]" = OrderedDict()
        # Generations with an admission decode in flight (single-flight:
        # a burst of readers crossing the admission threshold must not
        # stampede N whole-block decodes — losers fall back to the plain
        # per-row path until the winner publishes).
        self._decoding: set = set()
        # Blocks `offer` claimed for the fill thread, the planes' bytes
        # they will take, and the thread while it lives (it ends with
        # its queue; `_idle` tells `wait_filled`). Bounded by bytes:
        # `offer` queues no more than the budget has room for.
        self._fill: deque = deque()  # m3lint: disable=unbounded-queue
        self._fill_bytes = 0
        self._filler: Optional[threading.Thread] = None
        self._idle = threading.Condition(self._lock)
        self._bytes = 0
        scope = scope or instrument.ROOT.sub_scope("storage.block_cache")
        self._hits = scope.counter("hits")
        self._misses = scope.counter("misses")
        self._evictions = scope.counter("evictions")
        self._invalidations = scope.counter("invalidations")
        self._admitted = scope.counter("admitted")
        self._retained = scope.counter("retained")
        self._fill_errors = scope.counter("fill_errors")
        # the fill thread waited its whole stand-back for a quiet moment
        # and got none (utils/foreground.py)
        self._fill_quiet_timeouts = scope.sub_scope("fill").counter(
            "quiet_timeouts")
        self._bytes_gauge = scope.gauge("bytes")
        # Per-instance tallies (the instrument scope aggregates
        # process-wide by name — the postings-cache convention).
        self._n = {"hits": 0, "misses": 0, "evictions": 0,
                   "invalidations": 0, "admitted": 0, "retained": 0}
        self.budget.register(tenant, self.resident_bytes, self.evict_one)

    # ---------------------------------------------------------------- serving

    def decoded(self, blk, row_read: bool = False
                ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The block's decoded (ts_ns [S, W], vals [S, W]) planes — frozen,
        shared — or None when the block hasn't earned admission yet.
        Records the touch either way; an admission decodes the whole block
        once (from retained device buffers when present). `row_read`: the
        caller wants one row of it (SealedBlock.read), so an admission is
        a whole block decoded for that row, and is made only while the
        budget has room; a caller that decodes the whole block anyway
        (read_all) admits whenever the block has earned it. (A fetch's
        batched read: `lookup`, then `offer`.)"""
        dec = self.lookup(blk)
        if dec is None and self.wants(blk) and (
                not row_read or self.has_room(blk)):
            dec = self.admit(blk)
        return dec

    def lookup(self, blk, rows: int = 1
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The resident planes, or None; `rows` reads of the block are
        counted as hits, or as misses and touches, either way."""
        gen = blk.gen
        with self._lock:
            e = self._entries.get(gen)
            if e is not None and e.decoded is not None:
                self._entries.move_to_end(gen)
                self._n["hits"] += rows
                self._hits.inc(rows)
                tracing.count_cost("block_cache_hit", rows)
                return e.decoded
            self._n["misses"] += rows
            self._misses.inc(rows)
            # Per-span cache attribution: a slow query whose span shows
            # block_cache_miss > 0 gets the typed "cold-cache" reason.
            tracing.count_cost("block_cache_miss", rows)
            if gen in self._dead:
                return None
            self._touch[gen] = self._touch.pop(gen, 0) + rows
            while len(self._touch) > _TOUCH_MAX:
                self._touch.popitem(last=False)
            return None

    def wants(self, blk) -> int:
        """The touches of a block that has earned admission (`admit_after`
        of them) and that nobody is decoding, else 0."""
        gen = blk.gen
        with self._lock:
            touches = self._touch.get(gen, 0)
            if touches < self.admit_after or gen in self._decoding \
                    or gen in self._dead:
                return 0
            return touches

    def has_room(self, blk) -> bool:
        """Whether the block's planes fit under the budget as it stands,
        so that admitting it evicts nothing."""
        return self.budget.total() + plane_bytes(blk) <= self.budget.limit

    def admit(self, blk) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Decode the whole block once (single-flight: a loser gets None
        and reads its rows cold) and keep the planes; the budget then
        evicts least-recently-used entries until the total fits."""
        gen = blk.gen
        with self._lock:
            if gen in self._decoding or gen in self._dead:
                return None
            self._decoding.add(gen)
        return self._decode_claimed(blk)

    def _decode_claimed(self, blk
                        ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The one decode of a generation in `_decoding`, by whoever put
        it there (`admit`, or `offer` for the fill thread)."""
        gen = blk.gen
        with self._lock:
            e = self._entries.get(gen)
            encoded = e.encoded if e is not None else None
        # Decode outside the lock (device launch / host scan), then
        # publish.
        try:
            ts, vals = blk._decode_plane(encoded)
            out = self._put_decoded(gen, blk, ts, vals)
        finally:
            with self._lock:
                self._decoding.discard(gen)
        self.budget.reclaim()
        return out

    def offer(self, blocks) -> int:
        """What a fetch's batched read does with the blocks it read
        cold; returns how many it queued or admitted.

        While the budget has room, every one that has earned its place
        goes to the fill thread, which decodes them one at a time
        between the requests: a restarted node's first panels touch
        hundreds of blocks twice, and decoded on the request's thread
        they cost a panel seconds. No more is queued than fits.

        Once admitting means evicting, the fetch admits ONE itself, the
        most touched — every miss of a fetch whose store is larger than
        the budget would otherwise decode 625 rows to serve one and push
        out a block as warm as itself. The cache so turns over at the
        pace of its fetches, and a block earns its place by being asked
        for more than the others."""
        best, most, queued = None, 0, 0
        room = self.budget.limit - self.budget.total()
        with self._lock:
            room -= self._fill_bytes
            for blk in blocks:
                gen = blk.gen
                touches = self._touch.get(gen, 0)
                if touches < self.admit_after or gen in self._decoding \
                        or gen in self._dead:
                    continue
                need = plane_bytes(blk)
                if need <= room:
                    self._decoding.add(gen)
                    self._fill.append(blk)
                    self._fill_bytes += need
                    room -= need
                    queued += 1
                elif touches > most:
                    best, most = blk, touches
            start = queued and self._filler is None
            if start:
                self._filler = threading.Thread(
                    target=self._fill_loop, args=(dscope.current(),),
                    name=FILL_THREAD_NAME, daemon=True)
        if start:
            self._filler.start()
        if queued or best is None:
            return queued
        return 1 if self.admit(best) is not None else 0

    def _fill_loop(self, scope):
        """The fill thread: the queue's blocks in turn, each in a moment
        when no request is being served if one comes soon, in the scope
        of the fetch that started it (the cache's own); ends with the
        queue."""
        with scope:
            while True:
                if not foreground.wait_quiet(FILL_STANDS_BACK_S):
                    self._fill_quiet_timeouts.inc()
                with self._lock:
                    if not self._fill:
                        self._filler = None
                        self._idle.notify_all()
                        return
                    blk = self._fill.popleft()
                try:
                    self._decode_claimed(blk)
                except Exception:   # the block stays cold, and says so
                    self._fill_errors.inc()
                    _LOG.exception("block cache fill: block %d of gen %d",
                                   blk.block_start, blk.gen)
                finally:
                    with self._lock:
                        self._fill_bytes -= plane_bytes(blk)

    def wait_filled(self, timeout: float = 30.0) -> bool:
        """Block until the fill thread has ended (tests, and a caller
        that wants the cache's state settled)."""
        with self._lock:
            return self._idle.wait_for(lambda: self._filler is None,
                                       timeout)

    def _put_decoded(self, gen: int, blk, ts: np.ndarray, vals: np.ndarray
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        with self._lock:
            if gen in self._dead:
                # A seal/merge/expiry dropped this generation while we
                # decoded: never re-pin its arrays (the postings-cache
                # racing-seal contract). The decode result is still
                # returned to THIS caller — it is correct data.
                return (ts, vals)
            e = self._entries.get(gen)
            if e is None:
                e = self._entries[gen] = _Entry()
            if e.decoded is not None:
                old = sum(a.nbytes for a in e.decoded)
                e.nbytes -= old
                self._bytes -= old
            e.decoded = (ts, vals)
            added = ts.nbytes + vals.nbytes
            e.nbytes += added
            self._bytes += added
            if e.encoded is not None:
                # The decoded planes supersede the retained encode
                # buffers: nothing re-reads them once a plane is resident
                # (eviction drops the whole entry), so keeping both would
                # double-charge every hot block to the budget.
                freed = sum(int(getattr(a, "nbytes", 0)) for a in e.encoded)
                e.encoded = None
                e.nbytes -= freed
                self._bytes -= freed
            self._entries.move_to_end(gen)
            self._touch.pop(gen, None)
            self._n["admitted"] += 1
            self._admitted.inc()
            self._bytes_gauge.update(self._bytes)
            return e.decoded

    # --------------------------------------------------------------- retain

    def retain_encoded(self, blk, namespace: Optional[bytes] = None,
                       shard_id: int = -1) -> bool:
        """Adopt the just-encoded device buffers a seal left on `blk`
        (encode_block attaches them when a device backend is worth it) so
        the block stays decodable on its mesh devices. Returns True when
        the buffers were retained."""
        dev = blk.__dict__.pop("_encoded_dev", None)
        if dev is None or not self.enabled:
            return False
        words, npoints = dev
        added = int(getattr(words, "nbytes", 0)) + \
            int(getattr(npoints, "nbytes", 0))
        gen = blk.gen
        with self._lock:
            if gen in self._dead:
                return False
            e = self._entries.get(gen)
            if e is None:
                e = self._entries[gen] = _Entry()
            if e.encoded is not None:
                return False  # already retained
            e.encoded = (words, npoints)
            e.nbytes += added
            self._bytes += added
            e.meta = (namespace, shard_id, blk.block_start)
            self._entries.move_to_end(gen)
            self._n["retained"] += 1
            self._retained.inc()
            self._bytes_gauge.update(self._bytes)
        return True

    def encoded(self, blk) -> Optional[tuple]:
        """The retained device (words, npoints) for a block, if resident."""
        with self._lock:
            e = self._entries.get(blk.gen)
            if e is None or e.encoded is None:
                return None
            self._entries.move_to_end(blk.gen)
            return e.encoded

    # --------------------------------------------------------- invalidation

    def invalidate(self, gen: int) -> bool:
        """Drop one generation's residency and refuse later puts for it
        (seal/merge/expiry/evict/close hooks). Safe under callers' locks:
        pure dict work, no callbacks, no budget traffic."""
        with self._lock:
            self._dead[gen] = None
            while len(self._dead) > _DEAD_GENS_MAX:
                self._dead.popitem(last=False)
            self._touch.pop(gen, None)
            e = self._entries.pop(gen, None)
            if e is None:
                return False
            self._bytes -= e.nbytes
            self._n["invalidations"] += 1
            self._invalidations.inc()
            self._bytes_gauge.update(self._bytes)
            return True

    def invalidate_block(self, blk) -> bool:
        return self.invalidate(blk.gen)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._touch.clear()
            self._bytes = 0
            self._bytes_gauge.update(0)

    # -------------------------------------------------------------- eviction

    def evict_one(self) -> int:
        """Budget callback: drop the least-recently-used entry; returns
        bytes freed (0 when empty)."""
        with self._lock:
            if not self._entries:
                return 0
            _gen, e = self._entries.popitem(last=False)
            self._bytes -= e.nbytes
            self._n["evictions"] += 1
            self._evictions.inc()
            self._bytes_gauge.update(self._bytes)
            return e.nbytes

    # ----------------------------------------------------------------- intro

    def resident_bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {**self._n, "entries": len(self._entries),
                    "bytes": self._bytes}


# ------------------------------------------------------------ process cache

_BYPASS = threading.local()


def get_cache() -> DeviceBlockCache:
    """The block cache of the calling thread's scope (parallel/scope.py):
    the process's one, or the cache of a service that was given devices
    of its own, over that service's own HBM budget."""
    return dscope.current().owned("block_cache",
                                  lambda _sc: DeviceBlockCache())


def active() -> Optional[DeviceBlockCache]:
    """The process cache when enabled and not bypassed, else None (read
    paths fall back to plain decode — bypass is always correct)."""
    if getattr(_BYPASS, "depth", 0):
        return None
    c = get_cache()
    return c if c.enabled else None


@contextlib.contextmanager
def disabled():
    """Bypass the cache on this thread (correctness A/B: the bench and
    property tests compare cached reads against this path)."""
    _BYPASS.depth = getattr(_BYPASS, "depth", 0) + 1
    try:
        yield
    finally:
        _BYPASS.depth -= 1


def wants_encoded() -> bool:
    """Whether seals should keep their encoded device buffers for the
    cache: worth it on a real accelerator (saves the H2D re-upload of
    every warm decode); on host CPU the retained 'device' buffer is just
    a duplicate host allocation. M3_TPU_BLOCK_CACHE_RETAIN=1/0 forces
    either way (tests and the virtual-device smoke use it)."""
    forced = os.environ.get("M3_TPU_BLOCK_CACHE_RETAIN")
    if forced is not None:
        return forced == "1" and active() is not None
    if active() is None:
        return False
    import jax

    return jax.default_backend() != "cpu"
