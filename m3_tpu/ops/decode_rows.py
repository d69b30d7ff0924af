"""These N rows of one geometry, decoded: the one host-side way into
`tsz.decode_plane` for everything that reads sealed rows — a block's
row read and whole-block decode (storage/block.py), a node's batched
cold read (storage/read_batch.py), a session's decode of the frames a
fetch holds (client/session.py), repair and the peers bootstrap.

What a caller would otherwise answer for itself lives here once: how
many rows the program is compiled for (`ROW_BUCKETS`), how the rows are
padded (copies of the first), that a geometry's programs are compiled
before a served read needs them (`_warm`), that a device fault answers
from the host oracle (the `block.decode` guard) and what is counted
(`telemetry.record_bucket("block.decode_plane")`). A caller counts and
times what is its own around the call."""

from __future__ import annotations

import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from . import ref_codec, tsz
from ..parallel import guard, scope as dscope, telemetry
from ..utils import xtime

# The row counts a decode is compiled for: the powers of two from 8 to
# 1,024. A lone row is never a program of its own (one-row u32-pair
# programs are suspect on the chip: tsz.decode_plane), eight shapes a
# geometry serve every read from one series' one block to a thousand
# rows, and more rows go in calls of the largest.
ROW_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)
_warm_lock = threading.Lock()


def _host_oracle(words, npoints, window: int, unit_nanos: int):
    """Host oracle decode (ops/ref_codec, row by row): the fallback when
    the device decode faults or its breaker is open. Bit-identical on
    the valid region by the property-corpus contract; padding cells are
    zero (consumers never read past npoints[r])."""
    words = np.asarray(words)
    npoints = np.asarray(npoints)
    s = words.shape[0]
    ts = np.zeros((s, window), np.int64)
    vals = np.zeros((s, window), np.float64)
    for r in range(s):
        n = int(npoints[r])
        if n == 0:
            continue
        t, v = ref_codec.decode(ref_codec.EncodedBlock(
            words=words[r], nbits=0, npoints=n))
        ts[r, :n] = np.asarray(t, np.int64) * unit_nanos
        vals[r, :n] = np.asarray(v, np.float64)
    return ts, vals


def _call(words, npoints, window: int, unit_nanos: int, ran_on=None):
    """One program over a rung's rows through the compute-fault guard:
    primary is the fused device program (itself guarded at the
    codec.decode level for its Pallas-vs-XLA routing), fallback the
    host oracle."""
    telemetry.record_bucket(
        "block.decode_plane",
        (int(np.shape(words)[0]), int(np.shape(words)[-1]), int(window)))
    return guard.dispatch(
        "block.decode",
        lambda: tsz.decode_plane(words, npoints, window=window,
                                 unit_nanos=unit_nanos, ran_on=ran_on),
        lambda _err: _host_oracle(words, npoints, window, unit_nanos))


def _compiles_are_dear() -> bool:
    """On an accelerator a shape's first decode is a compile of seconds
    inside a served read; on the CPU it is cheap and a shape compiles
    where it is first met."""
    return jax.default_backend() != "cpu"


def _warm(words, npoints, window: int, unit_nanos: int):
    """Which rung a read needs depends on what the cache holds and how
    many replicas had answered at that instant: where compiles are dear,
    a geometry's first decode on the calling thread's device scope
    brings every rung through its compile at once, on rows of its own.
    A jitted program is compiled for the device it runs on, so what is
    warm is kept by the scope."""
    warmed = dscope.current().owned("decode_rows_warmed", lambda _sc: set())
    key = (int(window), int(unit_nanos), int(np.shape(words)[-1]))
    if key in warmed:
        return
    with _warm_lock:
        if key in warmed:
            return
        if _compiles_are_dear():
            for rows in ROW_BUCKETS:
                _call(np.repeat(words[:1], rows, 0),
                      np.repeat(npoints[:1], rows), window, unit_nanos)
        warmed.add(key)


def decode_rows(words, npoints, window: int, unit_nanos: int, *,
                ran_on: Optional[list] = None
                ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Rows of one geometry (window, time unit, words width), whichever
    blocks or replicas they come from, to (ts_ns [N, W] int64, vals
    [N, W] f64, calls made). The rows are padded to the smallest rung of
    ROW_BUCKETS that holds them with copies of the first (always valid);
    more than the largest go in calls of the largest, the last padded to
    its rung. Input already on a device with a rung's row count (the
    block cache's retained encode) is used where it is. `ran_on`, a
    list, receives the devices that hold each call's result."""
    n = int(np.shape(words)[0])
    if not n:
        return (np.zeros((0, window), np.int64),
                np.zeros((0, window), np.float64), 0)
    if isinstance(words, jax.Array) and n in ROW_BUCKETS:
        return (*_call(words, npoints, window, unit_nanos, ran_on), 1)
    words = np.asarray(words)
    npoints = np.asarray(npoints, np.int32)
    _warm(words, npoints, window, unit_nanos)
    top = ROW_BUCKETS[-1]
    cuts = []
    for lo in range(0, n, top):
        w, k = words[lo:lo + top], npoints[lo:lo + top]
        have = len(w)
        rows = next(b for b in ROW_BUCKETS if b >= have)
        if rows != have:
            w = np.concatenate([w, np.repeat(w[:1], rows - have, 0)])
            k = np.concatenate([k, np.repeat(k[:1], rows - have)])
        ts, vals = _call(w, k, window, unit_nanos, ran_on)
        cuts.append((ts[:have], vals[:have]))
    if len(cuts) == 1:
        return (*cuts[0], 1)
    return (np.concatenate([ts for ts, _ in cuts]),
            np.concatenate([vs for _, vs in cuts]), len(cuts))


def decode_stacked(tiles: List[dict], decode: Callable = decode_rows
                   ) -> Iterator[Tuple[dict, np.ndarray, np.ndarray,
                                       np.ndarray]]:
    """One decode a geometry, not one a tile: the decode is
    row-independent, so the tiles (storage/tiles.py) of one window, time
    unit and words width are stacked into one `decode` call whichever
    block or replica they come from; `decode` has `decode_rows`'
    signature and result, and a caller that times or counts its decodes
    passes `decode_rows` wrapped in what it keeps. Yields every tile in block-start order
    with its point counts and its rows of the planes: (tile, npoints,
    ts, vals)."""
    groups: Dict[tuple, List[dict]] = {}
    for tile in sorted(tiles, key=lambda d: d["bs"]):
        groups.setdefault(
            (int(tile["window"]), int(tile["time_unit"]),
             int(np.shape(tile["words"])[-1])), []).append(tile)
    for (window, unit, _width), members in groups.items():
        words = [np.asarray(t["words"]) for t in members]
        npts = [np.asarray(t["npoints"], np.int32) for t in members]
        one = len(members) == 1
        ts, vs, _calls = decode(words[0] if one else np.concatenate(words),
                                npts[0] if one else np.concatenate(npts),
                                window, xtime.Unit(unit).nanos)
        at = 0
        for tile, ks in zip(members, npts):
            yield tile, ks, ts[at:at + len(ks)], vs[at:at + len(ks)]
            at += len(ks)
