"""Replica reconciliation, client side.

The reference dbnode returns *compressed* segments; the client's
MultiReaderIterator / SeriesIterator decode and k-way merge across
replicas with same-timestamp conflict strategies
(src/dbnode/encoding/series_iterator.go:76,176, iterators.go:60-105).

TPU-first twist: instead of a per-series pull iterator, the tiles of a
fetch are stacked by geometry and decoded in one batched device call
(ops/decode_rows.py, from client/session.py::_one_pass_points), then
merged per series on host: what this module keeps."""

from __future__ import annotations

import enum
from typing import Sequence, Tuple

import numpy as np


class ConflictStrategy(enum.Enum):
    """Cross-replica same-timestamp resolution (encoding/iterators.go:60-105).

    4/4 parity with the reference's IterateLastPushed / IterateHighest /
    IterateLowest / IterateHighestFrequencyValue: HIGHEST_FREQUENCY_VALUE
    picks the value the most replicas agree on at a timestamp, and a
    frequency tie falls back to the last-pushed value among the tied
    candidates, matching the reference's tie behavior."""

    LAST_PUSHED = "last_pushed"
    HIGHEST_VALUE = "highest_value"
    LOWEST_VALUE = "lowest_value"
    HIGHEST_FREQUENCY_VALUE = "highest_frequency_value"


def merge_replica_points(
    ts_parts: Sequence[np.ndarray],
    vs_parts: Sequence[np.ndarray],
    strategy: ConflictStrategy = ConflictStrategy.LAST_PUSHED,
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge datapoint runs from multiple replicas of one series: sort by
    timestamp, resolve duplicate timestamps per strategy."""
    ts_parts = [t for t in ts_parts if len(t)]
    if not ts_parts:
        return np.zeros(0, np.int64), np.zeros(0, np.float64)
    vs_parts = [v for v in vs_parts if len(v)]
    t = np.concatenate(ts_parts)
    v = np.concatenate(vs_parts)
    # Stable sort keeps replica arrival order within equal timestamps, so
    # "last occurrence" == last pushed.
    order = np.argsort(t, kind="stable")
    t, v = t[order], v[order]
    if len(t) < 2:
        return t, v
    # t is sorted: a slot starts where the timestamp changes (what
    # np.unique(t, return_inverse=True) gives, without its second sort)
    first = np.empty(len(t), bool)
    first[0] = True
    np.not_equal(t[1:], t[:-1], out=first[1:])
    if first.all():
        return t, v
    uniq, inverse = t[first], np.cumsum(first) - 1
    if strategy == ConflictStrategy.LAST_PUSHED:
        picked = np.zeros(len(uniq), np.float64)
        picked[inverse] = v  # later writes overwrite earlier per slot
    elif strategy == ConflictStrategy.HIGHEST_FREQUENCY_VALUE:
        # Majority vote per timestamp, resolved for ALL slots in one
        # vectorized grouping pass (with full replica overlap EVERY slot
        # is conflicted, so a per-slot Python scan would be quadratic):
        # group points into (slot, value) runs, count each run, then per
        # slot keep the run with the highest count — ties by the run
        # whose last push arrived latest (last-pushed fallback).
        arrival = np.arange(len(v))
        order = np.lexsort((arrival, v, inverse))
        sv, si, sa = v[order], inverse[order], arrival[order]
        new_run = np.empty(len(sv), bool)
        new_run[0] = True
        np.logical_or(si[1:] != si[:-1], sv[1:] != sv[:-1],
                      out=new_run[1:])
        run_starts = np.flatnonzero(new_run)
        run_slot = si[run_starts]
        run_val = sv[run_starts]
        run_count = np.diff(np.append(run_starts, len(sv)))
        run_last_arrival = sa[np.append(run_starts[1:], len(sv)) - 1]
        # Per slot take the lexicographically greatest (count, last
        # arrival) run: sort runs so it lands last within each slot.
        sel = np.lexsort((run_last_arrival, run_count, run_slot))
        slot_sorted = run_slot[sel]
        last_of_slot = np.empty(len(sel), bool)
        np.not_equal(slot_sorted[1:], slot_sorted[:-1],
                     out=last_of_slot[:-1])
        last_of_slot[-1] = True
        picked = np.zeros(len(uniq), np.float64)
        picked[slot_sorted[last_of_slot]] = run_val[sel[last_of_slot]]
    elif strategy == ConflictStrategy.HIGHEST_VALUE:
        picked = np.full(len(uniq), -np.inf)
        np.maximum.at(picked, inverse, v)
    else:
        picked = np.full(len(uniq), np.inf)
        np.minimum.at(picked, inverse, v)
    return uniq, picked
