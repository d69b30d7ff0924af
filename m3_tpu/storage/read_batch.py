"""One read of many series, as arrays: what `Shard.read` answers a series
at a time, for all the ids of a fetch in one routed sweep (the embedded
read path's twin of `rpc_fetch_tagged`'s frame, rpc/node_server.py).

The ids are routed once and grouped by shard. A shard's group resolves
its registry indices in one pass, reads its buffers under one hold of
the shard lock, and resolves the rows of every overlapping sealed block
in one step a (shard, block) (`SealedBlock.rows_of`). A block whose
decoded planes the block cache holds answers with row slices. The rows
of every other block are kept as PIECES (storage/tiles.py), gathered
into tiles and decoded ONE DISPATCH A GEOMETRY for the whole fetch
(`ops/decode_rows.py`: `decode_stacked` over `decode_rows`, as the
client's `Session._one_pass_points` decodes a fetch's frames), never
one a (series, block).

Admission (`DeviceBlockCache.offer`, after the fetch's cold decode). A
block earns its place by touches (`admit_after`, a row read counting
one). While the budget has room the blocks of a fetch that have earned
it go to the cache's fill thread: the fetch itself decodes only the
rows it wants. Once admitting means evicting, a fetch admits ONE block
itself, the most-touched of those it read cold: a store larger than
the budget would otherwise decode 625 rows to serve one at every miss
and push out a block as warm as the one it brings in.

The answers are `Shard.read`'s bit for bit: the same parts (sealed
blocks, disk, then the buffer, whose value wins a duplicate timestamp),
the same clip to [start, end), the same stable order."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ops.decode_rows import ROW_BUCKETS, decode_rows, decode_stacked
from ..persist.diskio import CorruptionError
from ..utils.tracing import clock_ns as _clock
from . import block_cache
from .block import count_cold
from .tiles import gather_tiles, piece_key

_NO_T = np.zeros(0, np.int64)
_NO_V = np.zeros(0, np.float64)


def _merge(parts_t: List[np.ndarray], parts_v: List[np.ndarray]
           ) -> Tuple[np.ndarray, np.ndarray]:
    """A series' parts (blocks, then the buffer) as one run: ascending,
    one point a timestamp, the last part winning a duplicate."""
    if not parts_t:
        return _NO_T, _NO_V
    if len(parts_t) == 1:
        t, v = parts_t[0], parts_v[0]
    else:
        t = np.concatenate(parts_t)
        v = np.concatenate(parts_v)
    if len(t) > 1:
        step = t[1:] > t[:-1]
        if not step.all():
            order = np.argsort(t, kind="stable")
            t, v = t[order], v[order]
            if (t[:-1] == t[1:]).any():
                keep = np.concatenate([t[:-1] != t[1:], [True]])
                t, v = t[keep], v[keep]
    return t, v


def read_many(ns, shard_set, ids: Sequence[bytes], start_ns: int,
              end_ns: int, acc=None
              ) -> List[Optional[Tuple[Optional[dict], np.ndarray,
                                       np.ndarray]]]:
    """(tags, t, v) for every id, in order; None for an id whose shard
    this namespace does not hold. `acc` (a detailed span) receives
    `lock_wait_ns`, `buffer_ns`, `block_ns` / `block_n` (the sealed
    blocks' part, a (series, block) pair counting one: resolve, cache
    lookup, gather, decode), `merge_ns`, and of the cold rows
    `cold_decode_ns`, `cold_rows_n`, `cold_dispatch_n` and
    `tile_gathers_n` (the array operations that gathered them)."""
    n = len(ids)
    out: List[Optional[tuple]] = [None] * n
    if not n:
        return out
    timed = acc is not None
    shards = ns.shards
    shard_ids = shard_set.lookup_memo(ids)
    order = np.argsort(shard_ids, kind="stable")
    by_shard = shard_ids[order]
    cuts = (np.flatnonzero(by_shard[1:] != by_shard[:-1]) + 1).tolist()
    order, by_shard = order.tolist(), by_shard.tolist()
    parts_t: List[list] = [[] for _ in range(n)]
    parts_v: List[list] = [[] for _ in range(n)]
    bufs: List[Optional[tuple]] = [None] * n
    tags: List[Optional[dict]] = [None] * n
    held = [False] * n
    cache = block_cache.active()
    pieces: Dict[tuple, list] = {}
    missed: list = []
    lock_ns = buffer_ns = block_ns = 0
    block_n = 0
    bsz = ns.opts.block_size_ns

    def scatter(bs: int, ts, vs, rows, poss, ks):
        """Rows of decoded planes to their series' parts, clipped where
        the block reaches past the range."""
        edge = bs < start_ns or bs + bsz > end_ns
        for row, pos, k in zip(rows, poss, ks):
            t, v = ts[row, :k], vs[row, :k]
            if edge:
                keep = (t >= start_ns) & (t < end_ns)
                t, v = t[keep], v[keep]
            parts_t[pos].append(t)
            parts_v[pos].append(v)

    def serve(blk, bs: int, rows: np.ndarray, at: np.ndarray):
        """A block's wanted rows: slices of its resident planes, or a
        piece of the fetch's cold decode."""
        nonlocal block_n
        block_n += len(rows)
        dec = cache.lookup(blk, len(rows)) if cache is not None else None
        if dec is None:
            pieces.setdefault(piece_key(blk), []).append((blk, rows, at))
            missed.append(blk)
        else:
            scatter(bs, dec[0], dec[1], rows.tolist(), at.tolist(),
                    blk.npoints[rows].tolist())

    row0 = np.zeros(1, np.int64)
    for a, b in zip([0] + cuts, cuts + [n]):
        shard = shards.get(by_shard[a])
        if shard is None:
            continue
        poss = order[a:b]
        for pos in poss:
            held[pos] = True
        if shard._retriever is not None:
            # block starts that live only on disk: a series' row of the
            # fileset as a one-row block (a series the registry does not
            # know may still be on disk), decoded with the rest
            t2 = _clock() if timed else 0
            for bs, sid, pos, blk in _disk_rows(
                    shard, [ids[pos] for pos in poss], poss, start_ns,
                    end_ns):
                serve(blk, bs, row0, np.array([pos]))
            if timed:
                block_ns += _clock() - t2
        registry = shard.registry
        idxs = registry.lookup_batch([ids[pos] for pos in poss])
        if (idxs < 0).any():        # indexed, never written here
            known = np.flatnonzero(idxs >= 0)
            poss = [poss[j] for j in known.tolist()]
            idxs = idxs[known]
            if not len(idxs):
                continue
        idx_list = idxs.tolist()
        t0 = _clock() if timed else 0
        # the blocks and the buffers under one hold of the shard lock
        # (tick expires blocks and makes buckets beside us); sealed
        # blocks are immutable once referenced
        with shard.write_lock:
            t1 = _clock() if timed else 0
            blocks = dict(shard.blocks)
            for pos, got in zip(poss, shard.buffer.read_many(
                    idx_list, start_ns, end_ns)):
                bufs[pos] = got
        t2 = _clock() if timed else 0
        for pos, tg in zip(poss, map(registry.tags_of, idx_list)):
            tags[pos] = tg
        poss_a = np.asarray(poss)
        top = max(idx_list)
        for bs in sorted(blocks):
            if bs + bsz <= start_ns or bs >= end_ns:
                continue
            blk = blocks[bs]
            try:
                blk._verify_rows()
            except CorruptionError:
                # as Shard.read: the block is dropped, the window is
                # served from what else covers it
                shard._drop_corrupt_block(bs, blk)
                continue
            rows, present = blk.rows_of(idxs, top)
            if len(rows):
                serve(blk, bs, rows,
                      poss_a if present is None else poss_a[present])
        if timed:
            lock_ns += t1 - t0
            buffer_ns += t2 - t1
            block_ns += _clock() - t2
    t3 = _clock() if timed else 0
    cold_ns = 0
    if pieces:
        def decode(words, npoints, window, unit_nanos):
            nonlocal cold_ns
            t = _clock() if timed else 0
            got = decode_rows(words, npoints, window, unit_nanos)
            if timed:
                cold_ns += _clock() - t
            count_cold(len(words), got[2], acc)
            return got

        for tile, ks, ts, vs in decode_stacked(
                gather_tiles(pieces, ROW_BUCKETS[-1], acc=acc), decode):
            scatter(tile["bs"], ts, vs, range(len(ks)),
                    tile["rows"].tolist(), ks.tolist())
        if cache is not None:
            cache.offer(missed)
    t4 = _clock() if timed else 0
    for pos in range(n):
        if not held[pos]:
            continue
        buf = bufs[pos]
        pt, pv = parts_t[pos], parts_v[pos]
        if buf is not None and len(buf[0]):
            pt.append(buf[0])
            pv.append(buf[1])
        t, v = _merge(pt, pv)
        out[pos] = (tags[pos], t, v)
    if timed:
        acc.add_cost("lock_wait_ns", lock_ns)
        acc.add_cost("buffer_ns", buffer_ns)
        acc.add_cost("block_ns", block_ns + t4 - t3)
        acc.add_cost("block_n", block_n)
        acc.add_cost("cold_decode_ns", cold_ns)
        if not pieces:      # a fetch that read nothing cold says so
            for kind in ("cold_rows_n", "cold_dispatch_n"):
                acc.add_cost(kind, 0)
        acc.add_cost("merge_ns", _clock() - t4)
    return out


def _disk_rows(shard, sids: List[bytes], poss: List[int], start_ns: int,
               end_ns: int):
    """(block start, series id, position, one-row block) for every series
    of the group in every block start that lives only on disk, through
    the shard's retriever (Shard.read's fall-through)."""
    retriever, ns_name = shard._retriever, shard._retriever_ns
    bsz = shard.opts.block_size_ns
    cutoff = shard._retention_cutoff
    with shard.write_lock:
        blocks = set(shard.blocks)
    for bs in sorted(retriever.block_starts(ns_name, shard.shard_id)):
        if bs in blocks or bs + bsz <= start_ns or bs >= end_ns:
            continue
        if cutoff is not None and bs + bsz <= cutoff:
            continue  # past retention; cleanup just hasn't run yet
        for sid, pos in zip(sids, poss):
            blk = retriever.block(ns_name, shard.shard_id, bs, sid)
            if blk is not None:
                yield bs, sid, pos, blk
