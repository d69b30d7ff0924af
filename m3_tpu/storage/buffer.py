"""Mutable head buffer: columnar staging + batch device encode.

TPU-first redesign of the reference's per-series mutable encoders
(src/dbnode/storage/series/buffer.go: dbBuffer with 3 rotating block-aligned
buckets, each holding one-or-more M3TSZ encoders that absorb out-of-order
writes and merge on drain). Encoding per-datapoint on device would be a
host<->device ping-pong per write; instead each shard stages writes in plain
columnar arrays (series index, timestamp, value) bucketed by block start —
O(1) appends, no per-write compression — and the whole bucket is encoded in
ONE batched kernel launch when the block seals (tick) or snapshots.

Out-of-order and duplicate writes land naturally in the columns; the sort at
seal time replaces the reference's multi-encoder merge (buffer.go:244-307),
with last-arrival-wins on duplicate timestamps matching the reference's
"latest write wins within a bucket" drain behavior. The acceptance window
(buffer_past/buffer_future) bounds live buckets to ~3, mirroring
buffer.go:51's bucketsLen=3 invariant structurally rather than by fixed
array.

A read is the other half: a bucket's rows are grouped by series once, by
the first read that meets them (`BlockBucket.group`), and a series' read
is a slice of that grouping — the reference reads a series' own encoders
(buffer.go ReadEncoded); here the per-series view is derived lazily so
that the append stays three slice stores."""

from __future__ import annotations

import dataclasses
import time
from bisect import bisect_left
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..utils import xtime
from ..utils.instrument import ROOT

# Reads of one series in one bucket: answered from the bucket's index by
# series alone, or with a scan of the rows appended since it was built.
_READ_INDEXED = ROOT.counter("storage.buffer.read.indexed")
_READ_TAIL_SCANS = ROOT.counter("storage.buffer.read.tail_scans")
_INDEX_BUILDS = ROOT.counter("storage.buffer.index.builds")
_INDEX_ROWS = ROOT.counter("storage.buffer.index.rows")
# What an open bucket that is being appended to costs its reads: the
# rows a tail scan looked through, and the time spent grouping a bucket
# again (under the shard lock) once its tail had outgrown the index.
_READ_TAIL_ROWS = ROOT.counter("storage.buffer.read.tail_rows")
_INDEX_REGROUP_NS = ROOT.counter("storage.buffer.index.regroup_ns")
_NO_ROWS = np.zeros(0, np.intp)


class _Cols:
    """Growable (series_idx, time, value) columns with doubling storage."""

    __slots__ = ("sidx", "ts", "vals", "n")

    def __init__(self, cap: int = 1024):
        self.sidx = np.empty(cap, np.int32)
        self.ts = np.empty(cap, np.int64)
        self.vals = np.empty(cap, np.float64)
        self.n = 0

    def _grow(self, need: int):
        cap = len(self.sidx)
        if self.n + need <= cap:
            return
        new = max(cap * 2, self.n + need)
        for name in ("sidx", "ts", "vals"):
            arr = getattr(self, name)
            out = np.empty(new, arr.dtype)
            out[: self.n] = arr[: self.n]
            setattr(self, name, out)

    def append(self, si: int, t: int, v: float):
        self._grow(1)
        self.sidx[self.n] = si
        self.ts[self.n] = t
        self.vals[self.n] = v
        self.n += 1

    def extend(self, si: np.ndarray, t: np.ndarray, v: np.ndarray):
        k = len(si)
        self._grow(k)
        self.sidx[self.n : self.n + k] = si
        self.ts[self.n : self.n + k] = t
        self.vals[self.n : self.n + k] = v
        self.n += k

    def view(self):
        return self.sidx[: self.n], self.ts[: self.n], self.vals[: self.n]


@dataclasses.dataclass
class BlockBucket:
    """One block-start's staging columns (analog of a buffer bucket)."""

    block_start: int
    cols: _Cols = dataclasses.field(default_factory=_Cols)
    # Rows its newest persisted snapshot holds (the mediator's mark): the
    # columns only append, so a bucket still at this count is that
    # snapshot's content and is not written again.
    snapshotted_rows: int = 0
    # The index by series over rows [0:indexed_n), built by reads and
    # never by an append: `order` is the stable argsort of the series
    # column's prefix (a series' rows stay in arrival order), `bounds[i]:
    # bounds[i + 1]` series i's slice of it. The columns only ever
    # append, so the prefix never changes; positions, not views, so a
    # `_grow` leaves it right. Rows past indexed_n are the tail.
    indexed_n: int = 0
    order: Optional[np.ndarray] = None
    bounds: Optional[np.ndarray] = None

    @property
    def num_writes(self) -> int:
        return self.cols.n

    def group(self) -> int:
        """Bring the index up to date for a read (under the shard lock)
        and return the tail's length. No index yet, or a tail longer
        than the indexed prefix: the whole bucket is grouped again, which
        is amortised as the columns' own doubling is. Otherwise the
        index stands and the read scans the tail."""
        n = self.cols.n
        tail = n - self.indexed_n
        if self.order is not None and tail <= self.indexed_n:
            return tail
        t0 = time.perf_counter_ns()
        sidx = self.cols.sidx[:n]
        self.order = np.argsort(sidx, kind="stable")
        counts = np.bincount(sidx)
        self.bounds = np.zeros(len(counts) + 1, np.intp)
        np.cumsum(counts, out=self.bounds[1:])
        self.indexed_n = n
        _INDEX_BUILDS.inc()
        _INDEX_ROWS.inc(n)
        _INDEX_REGROUP_NS.inc(time.perf_counter_ns() - t0)
        return 0


def dedup_sorted(sidx, ts, vals):
    """Stable-sorted columns -> per-point last-arrival-wins dedup."""
    order = np.lexsort((np.arange(len(ts)), ts, sidx))  # stable by arrival
    sidx, ts, vals = sidx[order], ts[order], vals[order]
    if len(ts) > 1:
        nxt_same = (sidx[:-1] == sidx[1:]) & (ts[:-1] == ts[1:])
        keep = np.concatenate([~nxt_same, [True]])
        sidx, ts, vals = sidx[keep], ts[keep], vals[keep]
    return sidx, ts, vals


def to_dense(sidx, ts, vals):
    """Grouped columns -> dense [S, W] tiles + per-series counts.

    Returns (series_indices [S], timestamps [S, W], values [S, W],
    npoints [S]) with W = max points per series; padding replicates each
    series' last point so the codec's delta math stays in range."""
    series, counts = np.unique(sidx, return_counts=True)
    s, w = len(series), int(counts.max(initial=1))
    tdense = np.zeros((s, w), np.int64)
    vdense = np.zeros((s, w), np.float64)
    row = np.repeat(np.arange(s), counts)
    col = np.arange(len(sidx)) - np.repeat(np.cumsum(counts) - counts, counts)
    tdense[row, col] = ts
    vdense[row, col] = vals
    # Pad tail with the last real point per series.
    lastc = counts - 1
    pad_t = tdense[np.arange(s), lastc]
    pad_v = vdense[np.arange(s), lastc]
    colg = np.arange(w)[None, :]
    padmask = colg >= counts[:, None]
    tdense = np.where(padmask, pad_t[:, None], tdense)
    vdense = np.where(padmask, pad_v[:, None], vdense)
    return series, tdense, vdense, counts.astype(np.int32)


def one_block_start(t_min: int, t_max: int, block_size_ns: int) -> Optional[int]:
    """The block start that rows between t_min and t_max share, or None
    where they straddle a boundary."""
    if t_min // block_size_ns != t_max // block_size_ns:
        return None
    return xtime.truncate(t_min, block_size_ns)


class ShardBuffer:
    """All mutable buckets for one shard, keyed by block start."""

    def __init__(self, block_size_ns: int, buffer_past_ns: int, buffer_future_ns: int):
        self.block_size_ns = block_size_ns
        self.buffer_past_ns = buffer_past_ns
        self.buffer_future_ns = buffer_future_ns
        self.buckets: Dict[int, BlockBucket] = {}

    def _bucket(self, block_start: int) -> BlockBucket:
        b = self.buckets.get(block_start)
        if b is None:
            b = self.buckets[block_start] = BlockBucket(block_start)
        return b

    def accepts(self, now_ns: int, t_ns: int) -> bool:
        """Write-time acceptance window (series.go Write bounds checks)."""
        return now_ns - self.buffer_past_ns <= t_ns <= now_ns + self.buffer_future_ns

    def write(self, series_idx: int, t_ns: int, value: float):
        self._bucket(xtime.truncate(t_ns, self.block_size_ns)).cols.append(series_idx, t_ns, value)

    def write_batch(self, sidx: np.ndarray, ts: np.ndarray, vals: np.ndarray,
                    block_start: Optional[int] = None) -> bool:
        """Append rows in arrival order. Rows of one block (the common
        batch: a scrape) are one bucket lookup and one extend; rows that
        straddle a boundary are split per block. `block_start`: the
        rows' one block where the caller has worked it out already (for
        a larger batch these rows are part of). True where the rows took
        the one-block route."""
        if block_start is None:
            if not len(ts):
                return True
            block_start = one_block_start(int(ts.min()), int(ts.max()),
                                          self.block_size_ns)
        if block_start is not None:
            self._bucket(block_start).cols.extend(sidx, ts, vals)
            return True
        starts = ts - ts % self.block_size_ns
        for bs in np.unique(starts):
            m = starts == bs
            self._bucket(int(bs)).cols.extend(sidx[m], ts[m], vals[m])
        return False

    def read(self, series_idx: int, start_ns: int, end_ns: int) -> Tuple[np.ndarray, np.ndarray]:
        """Merged in-order datapoints for one series in [start, end), under
        the shard lock: ascending timestamps, one point a timestamp, the
        last arrival winning a duplicate. The series' rows in a bucket
        are its slice of the bucket's index and, where rows were appended
        since the index was built, its rows of that tail after them
        (arrival order either way). Scrapes arrive in time order, so the
        rows are checked for that and only a series that fails goes
        through `dedup_sorted`."""
        parts: List[Tuple[np.ndarray, np.ndarray]] = []
        for bs in sorted(self.buckets):
            if bs + self.block_size_ns <= start_ns or bs >= end_ns:
                continue
            b = self.buckets[bs]
            if not b.cols.n:
                continue
            tail = b.group()
            cols = b.cols
            rows = _NO_ROWS
            if series_idx + 1 < len(b.bounds):
                rows = b.order[b.bounds[series_idx]:b.bounds[series_idx + 1]]
            if tail:
                _READ_TAIL_SCANS.inc()
                _READ_TAIL_ROWS.inc(tail)
                late = np.flatnonzero(
                    cols.sidx[b.indexed_n:cols.n] == series_idx)
                if len(late):
                    late += b.indexed_n
                    rows = np.concatenate((rows, late))
            else:
                _READ_INDEXED.inc()
            if not len(rows):
                continue
            t, v = cols.ts[rows], cols.vals[rows]
            if len(t) > 1 and not (t[1:] > t[:-1]).all():
                _, t, v = dedup_sorted(np.zeros(len(t), np.int32), t, v)
            # every row of a bucket lies in its block: a range that
            # covers the block clips nothing
            if start_ns > bs or end_ns < bs + self.block_size_ns:
                # bisect, not searchsorted: a numpy call that lets the
                # GIL go costs a hand-off when other handlers wait for it
                lo = bisect_left(t, max(start_ns, bs))
                hi = bisect_left(t, min(end_ns, bs + self.block_size_ns), lo)
                t, v = t[lo:hi], v[lo:hi]
            parts.append((t, v))
        if not parts:
            return np.zeros(0, np.int64), np.zeros(0, np.float64)
        if len(parts) == 1:
            return parts[0]
        return (np.concatenate([t for t, _ in parts]),
                np.concatenate([v for _, v in parts]))

    def read_many(self, series_idxs, start_ns: int,
                  end_ns: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        """`read` for each series of this shard, under one hold of its
        lock."""
        return [self.read(idx, start_ns, end_ns) for idx in series_idxs]

    def sealable(self, now_ns: int) -> List[int]:
        """Block starts no longer writable (block fully past buffer_past)."""
        return sorted(
            bs
            for bs in self.buckets
            if bs + self.block_size_ns + self.buffer_past_ns <= now_ns
        )

    def drain(self, block_start: int):
        """Remove and return the bucket's deduped dense tiles for encoding."""
        b = self.buckets.pop(block_start, None)
        if b is None or b.cols.n == 0:
            return None
        return to_dense(*dedup_sorted(*b.cols.view()))

    def snapshot(self, block_start: int):
        """Dense tiles of the bucket's current contents, leaving it mutable
        (storage/flush.go snapshot semantics)."""
        b = self.buckets.get(block_start)
        if b is None or b.cols.n == 0:
            return None
        return to_dense(*dedup_sorted(*b.cols.view()))
