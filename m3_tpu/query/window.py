"""The window layout of a plain range selector `m[range]`: the ONE
binding the interpreter (executor.py::_eval_range_selector) and the
compiled route (plan.py::bind) share, so they cannot disagree about what
a window sees.

Prometheus evaluates `f(m[range])` at output time T over the RAW samples
with timestamps in (T - range, T] (offset moves T back), and the rate
family extrapolates from the first and last sample TIMES. Two layouts
give the windowed kernels (ops/temporal.py) exactly those samples:

* **dense** — the fetched samples all lie on one grid `phase + k*cell`
  (the scrape cadence, refined so the query step is a whole number of
  cells). The plane is [series x cells] at that cell, one raw sample a
  lane, NaN where a series has none (a gap, a late start). Every window
  is W consecutive lanes and consecutive windows lie `stride` lanes
  apart; a lane's time follows from its position plus `edge`: how far
  the first lane lies after the window's open start and the window's end
  after the last lane (a query rarely starts on the cadence's phase).
* **packed** — samples on no common grid (scrape jitter), or a grid so
  fine the dense plane would be mostly holes: each output step's samples
  are gathered side by side, right-aligned in W = stride lanes
  ([series x steps*W], the layout packed subqueries already use), and
  `trel` carries every lane's own time as seconds before its window's
  end.

Membership is exact in both; nothing is consolidated away."""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils import tracing
from ..utils.instrument import ROOT
from .block import Block, BlockMeta, _entry_tags
from .plan import next_bucket

# The dense plane may hold this many lanes a fetched sample (holes are
# gaps, late starts and the lanes a finer cell leaves empty) plus a few
# free ones; past that the packed layout is the smaller plane.
DENSE_LANES_PER_SAMPLE = 4
DENSE_FREE_LANES = 4096

_WINDOWS = ROOT.counter("query.range_selector.windows")
_SAMPLES = ROOT.counter("query.range_selector.samples")
# Which layout a range selector took, once a selector evaluated.
_LAYOUT_DENSE = ROOT.sub_scope("query.range_selector",
                               layout="dense").counter("layouts")
_LAYOUT_PACKED = ROOT.sub_scope("query.range_selector",
                                layout="packed").counter("layouts")


@dataclasses.dataclass
class RangeWindows:
    """A range selector laid out for the windowed kernels."""

    block: Block                    # [S, lanes] f64 raw samples, NaN = none
    W: int                          # lanes a window
    stride: int                     # lanes between consecutive windows
    cell_ns: int                    # dense: a lane's width; packed: 0
    edge: Optional[np.ndarray]      # f32 (lead_s, tail_s); None: positions alone
    trel: Optional[np.ndarray]      # packed: [S, lanes] f32 s before window end

    @property
    def packed(self) -> bool:
        return self.trel is not None


def _time_groups(items) -> List[Tuple[np.ndarray, List[int]]]:
    """(timestamps, row indices) per distinct timestamp ARRAY: series of
    one storage batch share the object, and share the work here."""
    groups: Dict[int, Tuple[np.ndarray, List[int]]] = {}
    for i, (_, entry) in enumerate(items):
        t = entry["t"]
        g = groups.get(id(t))
        if g is None:
            groups[id(t)] = (np.asarray(t, dtype=np.int64), [i])
        else:
            g[1].append(i)
    return list(groups.values())


def _cadence(groups) -> Tuple[int, int, int]:
    """(grid, anchor, samples): the gcd of every fetched timestamp's
    distance from one of them, that anchor, and the sample count. grid 0
    means at most one distinct timestamp was fetched."""
    anchor, grid, n = None, 0, 0
    for t, rows in groups:
        if not t.size:
            continue
        n += t.size * len(rows)
        if anchor is None:
            anchor = int(t[0])
        grid = math.gcd(grid, int(np.gcd.reduce(t - anchor)))
    return grid, anchor or 0, n


def range_windows(series: Dict[bytes, dict], params, range_ns: int,
                  offset_ns: int,
                  consolidate: Callable[[BlockMeta, int],
                                        Tuple[list, np.ndarray]]
                  ) -> RangeWindows:
    """Lay the fetched `series` ({id: {tags, t, v}}) out for the output
    steps of `params`. `consolidate(meta, cell)` grids the series onto a
    dense meta (the engine's cached consolidation)."""
    with tracing.phase("window"):
        rw, seen = _layout(series, params, range_ns, offset_ns, consolidate)
    sp = tracing.detail()
    if sp is not None:
        sp.add_cost("window_samples_n", seen)
    return rw


def _layout(series, params, range_ns, offset_ns, consolidate):
    steps, step = params.steps, params.step_ns
    x0 = params.start_ns - offset_ns
    items = sorted(series.items())
    groups = _time_groups(items)
    grid, anchor, n = _cadence(groups)
    _WINDOWS.inc(len(items) * steps)
    _SAMPLES.inc(n)
    sp = tracing.detail()
    if sp is not None:
        sp.add_cost("range_samples_n", n)
        sp.add_cost("window_samples_due_n",
                    _samples_due(groups, x0, steps, step, range_ns))
    cell = math.gcd(grid, step) if steps > 1 else grid
    if cell == 0:
        cell = range_ns
    phase = anchor % cell
    k_end = (x0 - phase) // cell             # last grid point <= x0
    k_open = (x0 - range_ns - phase) // cell  # last grid point <= x0 - range
    W = k_end - k_open
    stride = step // cell if steps > 1 else 1
    lanes = W + (steps - 1) * stride
    if W < 1 or len(items) * lanes > (DENSE_LANES_PER_SAMPLE * n
                                      + DENSE_FREE_LANES):
        _LAYOUT_PACKED.inc()
        # the packed layout's share of the `window` phase
        with tracing.phase("window_pack"):
            return _packed(items, groups, x0, steps, step, range_ns)
    _LAYOUT_DENSE.inc()
    first = phase + (k_open + 1) * cell
    meta = BlockMeta(first, cell, lanes)
    tags, values = consolidate(meta, cell)
    edge = np.array([(first - (x0 - range_ns)) / 1e9,
                     (x0 - (phase + k_end * cell)) / 1e9], np.float32)
    rw = RangeWindows(Block(meta, tags, values), W, stride, cell, edge, None)
    seen = _dense_seen(values, W, stride, steps) if sp is not None else 0
    return rw, seen


def _bounds(t: np.ndarray, x: np.ndarray, range_ns: int):
    """Per output time, the [lo, hi) run of sorted `t` in (x - range, x]."""
    return (np.searchsorted(t, x - range_ns, side="right"),
            np.searchsorted(t, x, side="right"))


def _packed(items, groups, x0: int, steps: int, step: int, range_ns: int):
    x = x0 + np.arange(steps, dtype=np.int64) * step
    runs = []
    most = 1
    for t, rows in groups:
        order = None
        if t.size > 1 and not (t[1:] >= t[:-1]).all():
            order = np.argsort(t, kind="stable")
            t = t[order]
        lo, hi = _bounds(t, x, range_ns)
        if t.size:
            most = max(most, int((hi - lo).max()))
        runs.append((t, rows, order, lo, hi))
    W = next_bucket(most)
    lanes = steps * W
    values = np.full((len(items), lanes), np.nan)
    trel = np.zeros((len(items), lanes), np.float32)
    seen = 0
    for t, rows, order, lo, hi in runs:
        if not t.size:
            continue
        cols = hi[:, None] - W + np.arange(W)[None, :]        # [steps, W]
        valid = (cols >= lo[:, None]).ravel()
        src = np.clip(cols, 0, t.size - 1).ravel()
        before = np.where(valid, (np.repeat(x, W) - t[src]) / 1e9,
                          0.0).astype(np.float32)
        if order is not None:
            src = order[src]
        vs = np.stack([np.asarray(items[i][1]["v"], np.float64)
                       for i in rows])
        values[rows] = np.where(valid[None, :], vs[:, src], np.nan)
        trel[rows] = before[None, :]
        seen += int(valid.sum()) * len(rows)
    tags = [_entry_tags(entry) for _, entry in items]
    meta = BlockMeta(x0 - range_ns, 0, lanes)
    rw = RangeWindows(Block(meta, tags, values), W, W, 0,
                      np.zeros(2, np.float32), trel)
    return rw, seen


def _samples_due(groups, x0: int, steps: int, step: int,
                 range_ns: int) -> int:
    """Sum over the output steps of the raw samples Prometheus' window
    holds there: what `window_samples_n` is held against."""
    x = x0 + np.arange(steps, dtype=np.int64) * step
    due = 0
    for t, rows in groups:
        if t.size:
            lo, hi = _bounds(np.sort(t), x, range_ns)
            due += int((hi - lo).sum()) * len(rows)
    return due


def _dense_seen(values: np.ndarray, W: int, stride: int, steps: int) -> int:
    """Samples in the windows of a dense plane: a lane counts once for
    every window that covers it."""
    per_lane = np.concatenate([[0], np.cumsum(
        np.isfinite(values).sum(axis=0))])
    starts = np.arange(steps) * stride
    return int((per_lane[starts + W] - per_lane[starts]).sum())
