"""Columnar tiles of sealed-block rows: what a read of many series
gathers from the blocks before anything is decoded, whoever decodes it
— a replica's fetch_tagged frame (rpc/node_server.py: the client
decodes), peer streaming, or the node's own batched cold read
(storage/read_batch.py: the rows the block cache does not hold).

A (shard, block)'s wanted rows are resolved in one step
(SealedBlock.rows_of) and kept as a PIECE (block, rows, positions in the
caller's series list) under what a tile's rows must share — block start,
window, time unit, words width; `gather_tiles` then makes one tile a
key, cut at a row bound. The decode side stacks tiles of one geometry
(window, time unit, words width) into one call, whoever asks
(ops/decode_rows.py::decode_stacked)."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np


def piece_key(blk) -> tuple:
    return (int(blk.block_start), int(blk.window), int(blk.time_unit),
            int(np.shape(blk.words)[-1]))


def cut_rows(pieces: list, bound: int):
    """Lists of (block, rows, positions) pieces of at most `bound` rows
    each, in order; a piece that straddles a cut is split."""
    cur, room = [], bound
    for blk, rows, poss in pieces:
        while len(rows) >= room:
            cur.append((blk, rows[:room], poss[:room]))
            yield cur
            rows, poss = rows[room:], poss[room:]
            cur, room = [], bound
        if len(rows):
            cur.append((blk, rows, poss))
            room -= len(rows)
    if cur:
        yield cur


def _column(parts: list, dtype=np.int32) -> np.ndarray:
    """A tile's column from its pieces' gathers, converted once."""
    col = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return col if dtype is None else col.astype(dtype, copy=False)


def gather_tiles(pieces: Dict[tuple, list], max_rows: int,
                 before_tile: Optional[Callable[[int], None]] = None
                 ) -> List[dict]:
    """One tile a key of `pieces` (see piece_key), in key order, cut at
    `max_rows`: `rows` the positions its rows answer, `words`, `nbits`,
    `npoints` gathered from the blocks. `before_tile(n_bytes)` runs
    before a tile's three gathers materialize (a frame is charged, and
    can be refused, tile by tile)."""
    tiles: List[dict] = []
    for key in sorted(pieces):
        bs, window, time_unit, width = key
        for cut in cut_rows(pieces[key], max_rows):
            if before_tile is not None:
                before_tile(sum(len(at) for _, at, _ in cut) * width
                            * np.asarray(cut[0][0].words).itemsize)
            tiles.append({
                "bs": bs,
                "rows": _column([poss for _, _, poss in cut]),
                "words": _column(
                    [np.asarray(blk.words)[at] for blk, at, _ in cut], None),
                "nbits": _column(
                    [np.asarray(blk.nbits)[at] for blk, at, _ in cut]),
                "npoints": _column(
                    [np.asarray(blk.npoints)[at] for blk, at, _ in cut]),
                "window": window,
                "time_unit": time_unit,
            })
    return tiles
