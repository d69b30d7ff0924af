"""Block model: the unit of query dataflow (reference: src/query/block/
{types,column,series}.go — a Block is a (series x time-step) matrix viewable
by column or by series).

TPU-first redesign: the reference streams per-step column iterators between
transform goroutines; here a Block literally IS the dense [n_series, n_steps]
float32 matrix (NaN = no sample), so every transform is one batched device
op over the whole block instead of a per-step iterator hop. Series metadata
(tags) stays host-side alongside the matrix."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .model import Tags

NAN = np.nan


@dataclasses.dataclass(frozen=True)
class BlockMeta:
    """Time bounds of a block (block/types.go Metadata/Bounds): steps at
    start_ns, start_ns+step_ns, ..., count steps."""

    start_ns: int
    step_ns: int
    steps: int

    def step_time(self, i: int) -> int:
        return self.start_ns + i * self.step_ns

    def times(self) -> np.ndarray:
        return self.start_ns + self.step_ns * np.arange(self.steps, dtype=np.int64)

    @property
    def end_ns(self) -> int:
        """Exclusive end."""
        return self.start_ns + self.step_ns * self.steps


@dataclasses.dataclass
class Block:
    meta: BlockMeta
    series_tags: List[Tags]
    values: np.ndarray  # [n_series, steps] float, NaN = missing

    def __post_init__(self):
        assert self.values.ndim == 2
        assert self.values.shape == (len(self.series_tags), self.meta.steps), (
            self.values.shape, len(self.series_tags), self.meta.steps)

    @property
    def n_series(self) -> int:
        return len(self.series_tags)

    def with_values(self, values: np.ndarray, tags: Optional[List[Tags]] = None,
                    meta: Optional[BlockMeta] = None) -> "Block":
        return Block(meta or self.meta, tags if tags is not None else self.series_tags,
                     np.asarray(values))

    @staticmethod
    def empty(meta: BlockMeta) -> "Block":
        return Block(meta, [], np.zeros((0, meta.steps)))


class LazyBlock(Block):
    """Block whose values materialize on first access.

    The device->host result copy is started asynchronously at construction
    (ops/temporal.py _copy_async), so any host work done before `.values`
    is touched — parsing/fetching/gridding the NEXT query of a dashboard
    burst — overlaps the transfer instead of serializing behind it (the
    double-buffering lever for BASELINE config #3)."""

    def __init__(self, meta: BlockMeta, series_tags: List[Tags], fetch):
        # No super().__init__: values don't exist yet, so the dataclass
        # shape assert runs at materialization instead.
        self.meta = meta
        self.series_tags = series_tags
        self._fetch = fetch
        self._cache: Optional[np.ndarray] = None

    @property
    def values(self) -> np.ndarray:  # type: ignore[override]
        if self._cache is None:
            vals = np.asarray(self._fetch())
            assert vals.shape == (len(self.series_tags), self.meta.steps), (
                vals.shape, len(self.series_tags), self.meta.steps)
            self._cache = vals
            self._fetch = None
        return self._cache

    @values.setter
    def values(self, vals: np.ndarray):
        self._cache = np.asarray(vals)
        self._fetch = None


def _grid_snap(sorted_ts: np.ndarray, step_times: np.ndarray,
               lookback_ns: int) -> Tuple[np.ndarray, np.ndarray]:
    """Grid-snap rule shared by every consolidation path: for each step time
    t, pick the latest sample in (t - lookback, t]. Returns (take, src):
    step positions that receive a value and the sorted-sample index each
    reads from."""
    idx = np.searchsorted(sorted_ts, step_times, side="right") - 1
    safe = np.clip(idx, 0, sorted_ts.size - 1)
    take = (idx >= 0) & ((step_times - sorted_ts[safe]) < lookback_ns)
    return take, safe


def consolidate(timestamps: np.ndarray, values: np.ndarray, meta: BlockMeta,
                lookback_ns: int) -> np.ndarray:
    """Consolidate one series' raw datapoints onto the block's step grid:
    value at step time t = the latest sample in (t - lookback, t]
    (reference: src/query/ts/values.go consolidation + the Prometheus
    lookback-delta instant-vector rule its engine follows). Vectorized via
    searchsorted; returns [steps] with NaN where no sample qualifies."""
    out = np.full(meta.steps, NAN)
    if timestamps.size == 0:
        return out
    order = np.argsort(timestamps, kind="stable")
    ts = timestamps[order]
    vs = values[order]
    take, safe = _grid_snap(ts, meta.times(), lookback_ns)
    out[take] = vs[safe[take]]
    return out


def _entry_tags(entry: dict) -> Tags:
    """Tags object for a fetch-result entry, memoized INTO the entry —
    storages that serve the same entry dicts across queries (hot-block
    serving, dashboard bursts) pay tag interning once, not per query.
    Keyed on the tags object's identity so a later reassignment of
    entry["tags"] (e.g. FanoutStorage's cross-store merge) invalidates
    the memo instead of serving stale labels."""
    raw = entry["tags"]
    cached = entry.get("_tags")
    if cached is None or cached[0] is not raw:
        cached = (raw, Tags.of(dict(raw)))
        entry["_tags"] = cached
    return cached[1]


def consolidate_series(series: Dict[bytes, dict], meta: BlockMeta,
                       lookback_ns: int) -> Tuple[List[Tags], np.ndarray]:
    """Consolidate a fetch result ({id: {tags, t, v}}) onto the step grid.

    Series sharing an identical timestamp grid (the scrape-aligned common
    case) are consolidated as one vectorized batch: argsort/searchsorted run
    once per distinct grid instead of once per series, which is what makes
    10k-series range queries host-cheap. Grids are grouped by array object
    IDENTITY first (series from one storage batch share one grid object —
    zero per-series work), then by a cheap content key verified with
    array_equal.
    """
    items = sorted(series.items())
    tags_list = [_entry_tags(entry) for _, entry in items]
    rows: Optional[np.ndarray] = None  # lazy: fast path below skips it
    id_groups: Dict[int, List[int]] = {}
    raw_ts = []
    for i, (_, entry) in enumerate(items):
        t = entry["t"]
        raw_ts.append(t)
        id_groups.setdefault(id(t), []).append(i)
    # Singleton identity groups (distinct array objects) coalesce by
    # content key + array_equal check; shared-object groups skip both.
    groups: List[List[int]] = []
    by_key: Dict[tuple, List[List[int]]] = {}
    ts_arrays: List[Optional[np.ndarray]] = [None] * len(items)
    for idxs in id_groups.values():
        t = np.asarray(raw_ts[idxs[0]], dtype=np.int64)
        for i in idxs:
            ts_arrays[i] = t
        if len(idxs) > 1:
            groups.append(idxs)
            continue
        key = (t.size, int(t[0]) if t.size else 0,
               int(t[-1]) if t.size else 0)
        merged = False
        for g in by_key.setdefault(key, []):
            if np.array_equal(ts_arrays[g[0]], t):
                g.extend(idxs)
                merged = True
                break
        if not merged:
            by_key[key].append(idxs)
    for gl in by_key.values():
        groups.extend(gl)
    step_times = meta.times()
    for same in groups:
        rep = ts_arrays[same[0]]
        if rep.size == 0:
            continue
        # Skip the argsort for already-sorted grids (the storage layers
        # emit sorted timestamps) and fuse sort-order + grid-snap into ONE
        # gather — at 10k x 360 each avoided intermediate is a ~30MB copy.
        if rep.size > 1 and not (rep[1:] >= rep[:-1]).all():
            order = np.argsort(rep, kind="stable")
            sorted_rep = rep[order]
        else:
            order = None
            sorted_rep = rep
        take, safe = _grid_snap(sorted_rep, step_times, lookback_ns)
        vs = np.stack([np.asarray(items[i][1]["v"], np.float64) for i in same])
        cols = np.nonzero(take)[0]
        src = safe[cols] if order is None else order[safe[cols]]
        if (rows is None and len(groups) == 1 and cols.size == meta.steps
                and len(same) == len(items)):
            # ONE shared grid covering every step (the hot dashboard
            # shape): the gather IS the result — no NaN canvas, no fancy
            # double-index write (each a full-matrix pass at 10k series).
            return tags_list, vs[:, src]
        if rows is None:
            rows = np.full((len(items), meta.steps), NAN)
        rows[np.ix_(same, cols)] = vs[:, src]
    if rows is None:
        rows = np.full((len(items), meta.steps), NAN)
    return tags_list, rows


def block_from_series(series: Dict[bytes, dict], meta: BlockMeta,
                      lookback_ns: int) -> Block:
    """Assemble a Block from a client fetch_tagged result
    ({id: {tags, t, v}}), consolidating every series onto the step grid."""
    tags_list, rows = consolidate_series(series, meta, lookback_ns)
    return Block(meta, tags_list, rows)
