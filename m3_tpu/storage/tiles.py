"""Columnar tiles of sealed-block rows: what a read of many series
gathers from the blocks before anything is decoded, whoever decodes it
— a replica's fetch_tagged frame (rpc/node_server.py: the client
decodes), peer streaming, or the node's own batched cold read
(storage/read_batch.py: the rows the block cache does not hold).

A (shard, block)'s wanted rows are resolved in one step
(SealedBlock.rows_of) and kept as a PIECE (block, rows, positions in the
caller's series list; each an array or a list of ints) under what a
tile's rows must share — block start, window, time unit, words width;
`gather_tiles` then makes one tile a key, cut at a row bound, with a
number of array operations that does not grow with the tile's pieces.
The decode side stacks tiles of one geometry (window, time unit, words
width) into one call, whoever asks
(ops/decode_rows.py::decode_stacked)."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from ..utils.instrument import ROOT

# The array operations that built tile columns (fancy takes,
# concatenates, conversions of a list) and the rows they gathered.
_GATHERS = ROOT.counter("storage.tiles.gathers")
_ROWS = ROOT.counter("storage.tiles.rows")

# A piece of more rows than this is gathered with one fancy take a
# column; a smaller one row by row, as views of the block's words and
# plain ints, with nothing that lets the GIL go. A take of word rows
# that finds another thread waiting for the GIL costs a hand-off
# (60-80 us on the chip's host), a row a third of a microsecond: alone
# the take wins from 16 rows a piece, beside other threads only past
# 256, and a server's handlers are seldom alone (PERF.md section 6,
# PR 47, has the probe). A dashboard read's pieces hold a row or two, a
# whole block 625.
PIECE_TAKE_ROWS = 256


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


def _ints(seq) -> list:
    return seq if isinstance(seq, list) else seq.tolist()


def _column(parts: list, dtype=np.int32) -> np.ndarray:
    """A tile's column, made once: the int32 array of a list of ints,
    or (`dtype` None) its pieces' word rows in one array of their own
    dtype, no view of any block."""
    if dtype is not None:
        return np.array(parts, dtype)
    if len(parts) == 1 and parts[0].base is None:
        return parts[0]
    return np.concatenate(parts)


def gather_tiles(pieces: Dict[tuple, list], max_rows: int,
                 before_tile: Optional[Callable[[int], None]] = None,
                 acc=None) -> List[dict]:
    """One tile a key of `pieces` (see piece_key), in key order, cut at
    `max_rows`: `rows` the positions its rows answer, `words`, `nbits`,
    `npoints` gathered from the blocks. `before_tile(n_bytes)` runs
    before a tile's columns materialize (a frame is charged, and can be
    refused, tile by tile). `acc` (a detailed span) receives
    `tile_gathers_n`, the array operations the columns took."""
    tiles: List[dict] = []
    gathers = n_rows = 0
    for key in sorted(pieces):
        bs, window, time_unit, width = key
        for cut in cut_rows(pieces[key], max_rows):
            n = sum(len(at) for _, at, _ in cut)
            if before_tile is not None:
                before_tile(n * width * np.asarray(cut[0][0].words).itemsize)
            rows: List[int] = []
            nbits: List[int] = []
            npoints: List[int] = []
            words = []
            for blk, at, poss in cut:
                rows += _ints(poss)
                w, nb, k = (np.asarray(blk.words), np.asarray(blk.nbits),
                            np.asarray(blk.npoints))
                if len(at) > PIECE_TAKE_ROWS:
                    words.append(w[at])
                    nbits += nb[at].tolist()
                    npoints += k[at].tolist()
                    gathers += 3
                else:
                    for r in _ints(at):
                        words.append(w[r:r + 1])
                        nbits.append(nb.item(r))
                        npoints.append(k.item(r))
            tiles.append({
                "bs": bs,
                "rows": _column(rows),
                "words": _column(words, None),
                "nbits": _column(nbits),
                "npoints": _column(npoints),
                "window": window,
                "time_unit": time_unit,
            })
            gathers += 4
            n_rows += n
    _GATHERS.inc(gathers)
    _ROWS.inc(n_rows)
    if acc is not None:
        acc.add_cost("tile_gathers_n", gathers)
    return tiles
