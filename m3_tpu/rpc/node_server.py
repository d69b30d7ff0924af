"""Node RPC service + TCP server (reference:
src/dbnode/network/server/tchannelthrift/node/service.go).

Method parity with the thrift `Node` service: Write (:743),
WriteBatchRaw (:827), WriteTaggedBatchRaw (:900), Fetch (:323),
FetchTagged (:396), FetchBlocksRaw (:535), FetchBlocksMetadataRawV2
(:608), Query (:255), Truncate (:993), Health (:210). The key design
point is preserved: FetchTagged / FetchBlocks return *encoded* block
segments (packed u32 TSZ codewords) plus raw mutable-buffer columns —
decompression happens in the client with the batched device decode
kernel, exactly as the reference decodes client-side
(docs/m3db/architecture/engine.md:167)."""

from __future__ import annotations

import socket
import socketserver
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..parallel import scope as dscope
from ..storage.database import Database
from ..storage.series import charge_read
from ..storage.tiles import gather_tiles, piece_key
from ..utils import limits as xlimits
from ..utils import tracing
from ..utils.health import AdmissionGate, Priority
from ..utils.instrument import ROOT
from ..utils.limits import ResourceExhausted
from ..utils.retry import Deadline, DeadlineExceeded
from ..utils.tracing import clock_ns as _clock
from . import wire


class RPCError(Exception):
    """Server-side error carried back over the wire."""


# A fetch_tagged frame, counted: the tiles it carries and the (shard,
# block) gathers folded into them.
_FRAME_TILES = ROOT.counter("rpc.fetch_tagged.tiles")

# The most rows a fetch_tagged tile carries (~7.8 MB of words at a
# 120-point block's 475): a read of every series still charges, and can
# be refused, tile by tile, and concatenates no whole block start.
TILE_MAX_ROWS = 4096
# The most series a fetch_tagged buffer sweep reads under one
# acquisition of a shard's write lock.
BUFFER_CHUNK = 256


# Priority classification for admission control: the traffic whose loss
# turns an overload into an outage is CRITICAL and is never shed —
# health/admin probes (operators must see INTO an overloaded node) and
# replication/bootstrap streams (shedding them converts one overloaded
# replica into an under-replicated shard). Everything else is NORMAL
# serving traffic unless the request frame marks itself "bulk"
# (backfill), which sheds first at the high watermark.
_CRITICAL_METHODS = frozenset({
    "health", "namespaces", "truncate",
    "fetch_blocks", "fetch_blocks_metadata", "fetch_block_tiles",
    "fetch_block_metadata_tiles",
})


def method_priority(method: str, hint: Optional[str] = None) -> Priority:
    if method in _CRITICAL_METHODS:
        return Priority.CRITICAL
    if hint == "bulk":
        return Priority.BULK
    return Priority.NORMAL


class NodeService:
    """Dispatchable method table over a storage.Database, fronted by a
    bounded admission gate: in-flight requests past the high watermark
    shed bulk backfill, past capacity shed normal serving traffic too —
    with typed Backpressure so producers back off — while health/admin
    and replication always get through."""

    def __init__(self, db: Database, gate: Optional[AdmissionGate] = None,
                 limits: Optional[xlimits.QueryLimits] = None,
                 host_id: str = ""):
        self.db = db
        self.host_id = host_id  # the `host` tag of a traced request's span
        # monotonic, not wall clock: uptime is an ELAPSED measurement and
        # must not jump with NTP steps (m3lint wall-clock-latency).
        self.start_ns = time.monotonic_ns()
        # Default gate is generous (threaded server, sub-ms dispatches:
        # 1024 in flight means the node is drowning) but FINITE — overload
        # protection must be on by default, not a config opt-in.
        self.gate = gate if gate is not None else AdmissionGate(
            capacity=1024, name="rpc.node")
        self._limits = limits
        # Per-request deadline, thread-local because the ThreadingTCPServer
        # dispatches each connection on its own thread: rpc_* methods read
        # it to bail out of long loops once the caller's budget is gone.
        self._local = threading.local()

    # --------------------------------------------------------------- dispatch

    def dispatch(self, method: str, args: dict,
                 deadline: Optional[Deadline] = None,
                 priority_hint: Optional[str] = None,
                 trace_ctx=None):
        result, _sp = self.dispatch_traced(method, args, deadline,
                                           priority_hint, trace_ctx)
        return result

    def dispatch_traced(self, method: str, args: dict,
                        deadline: Optional[Deadline] = None,
                        priority_hint: Optional[str] = None,
                        trace_ctx=None, encode: bool = False):
        """dispatch + span plumbing: returns (result, finished span dict
        or None). A request frame carrying the "tr" context gets a
        remote-parented span around its whole dispatch — QueryScope exit
        annotates it with the request's cost tallies — and the finished
        tree rides the response frame back for the caller to graft
        (tracing module docstring). Untraced requests pay one NOOP test.
        `encode` (the TCP handler's): a traced request's result is put
        in wire form inside its span, which then carries the encode's
        `wire_encode_ns` and `bytes_out`, and comes back as
        `wire.Encoded`."""
        fn = getattr(self, "rpc_" + method, None)
        if fn is None:
            raise RPCError(f"unknown method {method!r}")
        # Check BEFORE the work: a request whose budget is already spent
        # in queueing/transit must not run an expensive fetch whose result
        # the caller stopped waiting for.
        if deadline is not None:
            deadline.check(method)
        ql = self._limits if self._limits is not None else xlimits.get_global()
        # Admission THEN limits scope: a shed request must cost nothing
        # beyond the gate check. The scope's child enforcers chain every
        # storage/index charge below this request to the global budgets
        # and release them all on the way out — 1k rejected queries leak
        # zero budget (asserted by scripts/overload_smoke.py).
        priority = method_priority(method, priority_hint)
        # the node rides from the start: the runtime probe counts this
        # thread's CPU under the role `rpc` of that node
        sp = tracing.TRACER.span_from(trace_ctx, "rpc." + method,
                                      host=self.host_id)
        # A shed BEFORE the scope runs (gate full) must log empty costs,
        # not the previous request's on this reused serving thread.
        xlimits.reset_last_totals()
        t0 = time.perf_counter_ns()
        try:
            # the handler thread works for this node: its devices
            with dscope.entered(self.db.scope), sp:
                with self.gate.held(priority=priority):
                    with ql.scope(f"rpc.{method}"):
                        self._local.deadline = deadline
                        # Down-stack admission (shard insert queues) sheds
                        # by the same priority the gate admitted at — BULK
                        # backfill that squeezed past the gate still sheds
                        # first at a full queue, and CRITICAL replication
                        # never sheds.
                        self._local.priority = priority
                        try:
                            result = fn(**args)
                        finally:
                            self._local.deadline = None
                            self._local.priority = None
                if sp.sampled:
                    if encode:
                        t_enc = tracing.clock_ns()
                        result = wire.Encoded(wire.encode(result))
                        sp.add_cost("wire_encode_ns",
                                    tracing.clock_ns() - t_enc)
                        sp.add_cost("bytes_out", len(result.data))
        except ResourceExhausted:
            tracing.SLOW_QUERIES.maybe(
                "rpc", method, time.perf_counter_ns() - t0,
                costs=xlimits.last_scope_totals(), reason="limit-shed",
                trace_id=sp.trace_id or None)
            raise
        except DeadlineExceeded:
            tracing.SLOW_QUERIES.maybe(
                "rpc", method, time.perf_counter_ns() - t0,
                costs=xlimits.last_scope_totals(), reason="deadline",
                trace_id=sp.trace_id or None)
            raise
        dur = time.perf_counter_ns() - t0
        tracing.SLOW_QUERIES.maybe(
            "rpc", method, dur,
            # Sampled: lazy subtree rollup (cache events live on storage
            # child spans); unsampled: the scope's charge totals.
            costs=((lambda: tracing.collect_costs(sp)) if sp.sampled
                   else xlimits.last_scope_totals()),
            trace_id=sp.trace_id or None)
        return result, (sp.to_dict() if sp.sampled else None)

    def _check_deadline(self, what: str):
        dl = getattr(self._local, "deadline", None)
        if dl is not None:
            dl.check(what)

    def _request_priority(self) -> Priority:
        pri = getattr(self._local, "priority", None)
        return Priority.NORMAL if pri is None else pri

    # ----------------------------------------------------------------- health

    def rpc_health(self):
        return {
            "ok": True,
            "bootstrapped": self.db.bootstrapped,
            "uptime_ns": time.monotonic_ns() - self.start_ns,
        }

    # ----------------------------------------------------------------- writes

    def rpc_write(self, ns: bytes, id: bytes, t_ns: int, value: float,
                  tags: Optional[dict] = None):
        """Concurrency is per shard, not global: the storage layer holds a
        per-shard write lock (storage/shard.py write_lock, the reference's
        shard.go:769 per-shard RWMutex), the reverse index and commit log
        serialize internally, so writes to different shards proceed in
        parallel across server threads."""
        self.db.write(ns, id, t_ns, value, tags,
                      priority=self._request_priority())
        return True

    def rpc_write_batch(self, ns: bytes, ids: list, ts: np.ndarray, vals: np.ndarray,
                        tags: Optional[list] = None,
                        shards: Optional[np.ndarray] = None):
        """`shards`: the rows' shards where the client has routed them
        already (Session.write_batch, once for all replicas, by the same
        murmur3 over the placement's shard count): the node then hashes
        nothing and the batch goes straight to the routing pass. Without
        them the batch is hashed here, the bulk route. `tags`: None, or
        None for a row whose series this node already holds tagged."""
        if shards is not None:
            shards = np.asarray(shards, np.int32)
            if (shards.shape != (len(ids),) or (len(ids) and (
                    shards.min() < 0
                    or shards.max() >= self.db.shard_set.num_shards))):
                raise RPCError("write_batch: `shards` does not fit the "
                               "batch or this node's shard count")
        self.db.write_batch(ns, ids, ts, vals, tags,
                            priority=self._request_priority(),
                            shard_ids=shards)
        return len(ids)

    # ------------------------------------------------------------------ reads

    def rpc_fetch(self, ns: bytes, id: bytes, start_ns: int, end_ns: int):
        t, v = self.db.read(ns, id, start_ns, end_ns)
        return {"t": t, "v": v}

    def rpc_fetch_tagged(self, ns: bytes, query: dict, start_ns: int, end_ns: int,
                         fetch_data: bool = True, limit: int = 0):
        """FetchTagged with a COLUMNAR result frame, built in one pass
        over the query's ids as arrays: per-series entries carry only
        identity (id + tags — host label algebra); the data plane rides
        beside them as ONE buffer sidecar (concatenated mutable-buffer
        columns + an offsets vector) and one TILE PER BLOCK START — the
        requested rows of every shard's block at that start, gathered
        out of the blocks' word matrices into one array a column
        (storage/tiles.py), with
        `rows` their positions in `series`: the tile shape peer
        streaming moves (rpc_fetch_block_tiles) and the client's batched
        device decode consumes (ops/decode_rows.py). Blocks of
        one start that differ in window, time unit or words width get a
        tile each, and a tile is cut at TILE_MAX_ROWS, so a frame is
        charged, and can be refused, tile by tile. The reference streams
        per-series segments instead (DIVERGENCES.md). A dashboard read
        puts a series or two in a shard, so nothing here is paid once a
        series but its buffer read, nor once a (shard, block) but its
        row resolve; a tile's array operations do not grow with its
        pieces."""
        q = wire.query_from_wire(query)
        nsobj = self.db.namespace(ns)
        # Under a detailed span (a traced request's rpc.fetch_tagged) the
        # phases become costs of that span: the index query, the
        # per-series identity and buffer reads, the tile gathers.
        acc = tracing.detail()
        timed = acc is not None
        t_start = _clock() if timed else 0
        ids = self.db.query_ids(ns, q, start_ns, end_ns, limit=limit)
        t_index = _clock() if timed else 0
        # Route once: the ids' shards from the write path's memo, rows
        # grouped by shard with one stable sort (Database.write_batch's
        # routing pass, read side).
        shards = dict(nsobj.shards)
        shard_ids = self.db.shard_set.lookup_memo(ids)
        if len(shards) < self.db.shard_set.num_shards:
            # An id of a shard this node does not hold leaves no row.
            held = np.isin(shard_ids, np.fromiter(shards, np.int32, len(shards)))
            ids = [ids[i] for i in np.flatnonzero(held).tolist()]
            shard_ids = shard_ids[held]
        n = len(ids)
        order = np.argsort(shard_ids, kind="stable")
        by_shard = shard_ids[order]
        cuts = (np.flatnonzero(by_shard[1:] != by_shard[:-1]) + 1).tolist()
        order, by_shard = order.tolist(), by_shard.tolist()
        out: List[Optional[dict]] = [None] * n
        groups = []  # (shard, registry indices, positions in `out`)
        owed = owed_n = 0  # identity bytes, and their series, not yet charged
        for a, b in zip([0] + cuts, cuts + [n]) if n else ():
            # Once a shard, not once a series: fetch_tagged is the
            # expensive fan-in, and a dead caller's request must stop
            # inside it, not run the whole result set to completion.
            self._check_deadline("fetch_tagged")
            shard = shards[by_shard[a]]
            poss = order[a:b]
            sids = [ids[pos] for pos in poss]
            idxs = shard.registry.lookup_batch(sids).tolist()
            if -1 in idxs:
                # Indexed on another replica's time range but not written
                # here: identity-only row, no buffer/tile contribution.
                known = []
                for j, idx in enumerate(idxs):
                    if idx < 0:
                        out[poss[j]] = {"id": sids[j], "tags": {}}
                    else:
                        known.append(j)
                poss, sids, idxs = ([col[j] for j in known]
                                    for col in (poss, sids, idxs))
                if not idxs:
                    continue
            # identity cost (id + tag pairs) charges bytes-read before any
            # segment payload does — a tags-only fetch is still metered.
            # Charged whenever a buffer chunk's worth of series is owed
            # and at the sweep's end, not once a group: a dashboard
            # read's groups hold a series or two.
            tags, n_bytes = shard.registry.identities(idxs)
            owed, owed_n = owed + n_bytes, owed_n + len(idxs)
            if owed_n >= BUFFER_CHUNK:
                charge_read(n_bytes=owed)
                owed = owed_n = 0
            for pos, sid, tg in zip(poss, sids, tags):
                out[pos] = {"id": sid, "tags": tg or {}}
            if fetch_data:
                groups.append((shard, idxs, poss))
        charge_read(n_bytes=owed)
        buf_t = [np.zeros(0, np.int64)] * n
        buf_v = [np.zeros(0, np.float64)] * n
        snapshots = []
        buffer_ns = 0   # the chunks' `ShardBuffer.read` loops, inside read_ns
        for shard, idxs, poss in groups:
            # Buffer reads take the shard write lock in bounded CHUNKS —
            # a dashboard-sized member set must not stall every
            # concurrent write for one uninterrupted sweep (the
            # per-series path re-acquired per row; chunking keeps that
            # bound without paying the lock once per series). The block
            # snapshot MERGES under every chunk's acquisition: a tick
            # sealing the buffer between chunks moves later chunks'
            # points into a block the first snapshot predates — the
            # union sees it (earlier chunks may then appear in both
            # their buffer read and the new block's tile; duplicate
            # timestamps carry identical values and the client's
            # replica merge dedups them, same as a replica overlap).
            # Each chunk charges its buffer bytes BEFORE the next
            # materializes (query_limits.go bytes-read: reject an
            # oversized fetch mid fan-in).
            blocks: Dict[int, object] = {}
            for c0 in range(0, len(idxs), BUFFER_CHUNK):
                self._check_deadline("fetch_tagged")
                part = poss[c0:c0 + BUFFER_CHUNK]
                with shard.write_lock:  # snapshot racing tick's expiry/seal
                    blocks.update(shard.blocks)
                    t_buf = _clock() if timed else 0
                    for pos, (t, v) in zip(part, shard.buffer.read_many(
                            idxs[c0:c0 + BUFFER_CHUNK], start_ns, end_ns)):
                        buf_t[pos], buf_v[pos] = t, v
                    if timed:
                        buffer_ns += _clock() - t_buf
                charge_read(n_bytes=sum(
                    buf_t[pos].nbytes + buf_v[pos].nbytes for pos in part))
            snapshots.append(blocks)
        t_tiles = _clock() if timed else 0
        # A (shard, block)'s rows are resolved in one step and kept as a
        # PIECE under what a tile's rows must share: block start, window,
        # time unit, words width. A block that holds every index of its
        # shard (every series written in every block) leaves the piece
        # its plain ints: no array is made a group or a piece.
        pieces: Dict[tuple, list] = {}
        for (shard, idxs, poss), blocks in zip(groups, snapshots):
            top = max(idxs)
            for bs, blk in blocks.items():
                if bs + shard.opts.block_size_ns <= start_ns or bs >= end_ns:
                    continue
                if not len(blk.series_indices):
                    continue
                at, present = blk.rows_of(idxs, top)
                if present is None:
                    piece = (blk, at, poss)
                elif len(at):
                    piece = (blk, at, np.asarray(poss)[present])
                else:
                    continue
                pieces.setdefault(piece_key(blk), []).append(piece)

        def before_tile(n_bytes: int):
            # Charge BEFORE the tile materializes (query_limits.go
            # bytes-read): an oversized result must be rejected mid
            # fan-in, not after every tile copy has been allocated —
            # the same incremental guard the per-series path had.
            self._check_deadline("fetch_tagged")
            charge_read(n_bytes=n_bytes)

        tiles = gather_tiles(pieces, TILE_MAX_ROWS, before_tile, acc)
        tile_ns = _clock() - t_tiles if timed else 0
        offs = np.zeros(n + 1, np.int64)
        if n:
            offs[1:] = np.cumsum([t.size for t in buf_t])
        bufs = {
            "offs": offs,
            "t": (np.concatenate(buf_t) if n else np.zeros(0, np.int64)),
            "v": (np.concatenate(buf_v) if n else np.zeros(0, np.float64)),
        }
        _FRAME_TILES.inc(len(tiles))
        if timed:
            acc.add_cost("series_n", n)
            acc.add_cost("index_ns", t_index - t_start)
            acc.add_cost("tile_ns", tile_ns)
            acc.add_cost("read_ns", _clock() - t_index - tile_ns)
            acc.add_cost("buffer_ns", buffer_ns)
            acc.add_cost("tiles_n", len(tiles))
        return {"series": out, "bufs": bufs, "tiles": tiles,
                "exhaustive": True}

    def rpc_query(self, ns: bytes, query: dict, start_ns: int, end_ns: int):
        """service.go:255 Query: ids + tags only (no data)."""
        r = self.rpc_fetch_tagged(ns, query, start_ns, end_ns, fetch_data=False)
        return {"series": [{"id": s["id"], "tags": s["tags"]} for s in r["series"]]}

    def rpc_aggregate(self, ns: bytes, query: dict, start_ns: int, end_ns: int,
                      name_only: bool = False, field_filter: list = (),
                      term_limit: int = 0):
        """AggregateRaw analog (service.go:474 Aggregate / AggregateRaw):
        distinct tag names (and optionally values) for series matching the
        query, computed server-side from the reverse index — no datapoints
        shipped. An AllQuery short-circuits to the index's field/term
        dictionaries instead of materializing postings."""
        fields = self.db.aggregate_tags(
            ns, wire.query_from_wire(query), start_ns, end_ns,
            name_only=name_only, filter_names=field_filter)
        out = []
        for name in sorted(fields):
            vals = sorted(fields[name])
            if term_limit:
                vals = vals[:term_limit]
            out.append({"name": name, "values": vals})
        return {"fields": out, "name_only": bool(name_only)}

    # -------------------------------------------- block/metadata peer streaming

    def rpc_fetch_blocks_metadata(self, ns: bytes, shard: int, start_ns: int,
                                  end_ns: int, page_token: int = 0,
                                  limit: int = 1024):
        """FetchBlocksMetadataRawV2: paged per-series sealed block metadata."""
        nsobj = self.db.namespace(ns)
        sh = nsobj.shards.get(shard)
        if sh is None:
            return {"series": [], "next_page_token": None}
        all_ids = sh.registry.all_ids()
        out = []
        i = page_token
        with sh.write_lock:  # snapshot racing tick's expiry/seal
            shard_blocks = dict(sh.blocks)
        while i < len(all_ids) and len(out) < limit:
            sid = all_ids[i]
            idx = sh.registry.get(sid)
            blocks = []
            for bs in sorted(shard_blocks):
                blk = shard_blocks[bs]
                if bs + sh.opts.block_size_ns <= start_ns or bs >= end_ns:
                    continue
                row = blk.row_of(idx)
                if row is None:
                    continue
                blocks.append({
                    "bs": bs,
                    "nbits": int(blk.nbits[row]),
                    "npoints": int(blk.npoints[row]),
                    "checksum": blk.row_checksum(row),
                })
            out.append({"id": sid, "tags": sh.registry.tags_of(idx) or {},
                        "blocks": blocks})
            i += 1
        next_token = i if i < len(all_ids) else None
        return {"series": out, "next_page_token": next_token}

    def rpc_fetch_blocks(self, ns: bytes, shard: int, requests: list):
        """FetchBlocksRaw: encoded rows for [(id, [block_starts])] requests."""
        nsobj = self.db.namespace(ns)
        sh = nsobj.shards.get(shard)
        out = []
        if sh is not None:
            with sh.write_lock:  # snapshot racing tick's expiry/seal
                shard_blocks = dict(sh.blocks)
        for req in requests:
            sid = req["id"]
            entry = {"id": sid, "blocks": []}
            if sh is not None:
                idx = sh.registry.get(sid)
                if idx is not None:
                    for bs in req["block_starts"]:
                        blk = shard_blocks.get(bs)
                        if blk is None:
                            continue
                        row = blk.row_of(idx)
                        if row is None:
                            continue
                        entry["blocks"].append({
                            "bs": bs,
                            "words": np.asarray(blk.words[row]),
                            "nbits": int(blk.nbits[row]),
                            "npoints": int(blk.npoints[row]),
                            "window": int(blk.window),
                            "time_unit": int(blk.time_unit),
                        })
            out.append(entry)
        return {"series": out}

    def rpc_fetch_block_metadata_tiles(self, ns: bytes, shard: int,
                                       start_ns: int, end_ns: int,
                                       page_token: int = 0,
                                       limit: int = 8192):
        """Columnar FetchBlocksMetadataRawV2: one page covers a
        contiguous registry-index window [page_token, page_token+limit)
        and returns the page's ids/tags plus, per sealed block, the
        positions (into the page's ids) and row checksums as ARRAYS —
        no per-series dicts on the wire. Registry indices are assigned
        densely in insertion order and block series_indices are sorted,
        so each block's page rows are one searchsorted slice."""
        nsobj = self.db.namespace(ns)
        sh = nsobj.shards.get(shard)
        if sh is None:
            return {"ids": [], "tags": [], "blocks": [],
                    "next_page_token": None}
        all_ids = sh.registry.all_ids()
        i0 = int(page_token)
        i1 = min(len(all_ids), i0 + int(limit))
        ids = all_ids[i0:i1]
        tags = [sh.registry.tags_of(i0 + j) or {} for j in range(len(ids))]
        with sh.write_lock:  # snapshot racing tick's expiry/seal
            shard_blocks = dict(sh.blocks)
        blocks = []
        total_bytes = sum(len(s) for s in ids)
        for bs in sorted(shard_blocks):
            self._check_deadline("fetch_block_metadata_tiles")
            blk = shard_blocks[bs]
            if bs + sh.opts.block_size_ns <= start_ns or bs >= end_ns:
                continue
            si = blk.series_indices
            lo = int(np.searchsorted(si, i0))
            hi = int(np.searchsorted(si, i1))
            if lo == hi:
                continue
            # Memoized per-block row checksums: repeated metadata pages
            # (every repair sweep, every bootstrap) reuse one pass.
            sums = blk.row_checksums()[lo:hi]
            total_bytes += sums.nbytes
            blocks.append({
                "bs": bs,
                "pos": np.ascontiguousarray(si[lo:hi] - i0, np.int32),
                "sums": sums,
            })
        charge_read(n_bytes=int(total_bytes))
        next_token = i1 if i1 < len(all_ids) else None
        return {"ids": ids, "tags": tags, "blocks": blocks,
                "next_page_token": next_token}

    def rpc_fetch_block_tiles(self, ns: bytes, shard: int, blocks: list):
        """Columnar FetchBlocksRaw: for [{"bs", "ids": [...]}] requests,
        return per-block TILES — one [rows, max_words] word matrix plus
        nbits/npoints columns and the row-aligned id list — instead of
        one dict per series. The whole tile is three fancy-indexes into
        the sealed block's arrays, and the client applies it as one
        batched registry insert + one block install (the peer-streaming
        data plane's unit of work; ids absent locally or rows the block
        doesn't hold are simply absent from the response ids)."""
        nsobj = self.db.namespace(ns)
        sh = nsobj.shards.get(shard)
        out = []
        if sh is None:
            return {"blocks": out}
        with sh.write_lock:  # snapshot racing tick's expiry/seal
            shard_blocks = dict(sh.blocks)
        for req in blocks:
            self._check_deadline("fetch_block_tiles")
            bs = int(req["bs"])
            blk = shard_blocks.get(bs)
            if blk is None:
                continue
            ids = req["ids"]
            idxs = sh.registry.lookup_batch(ids)
            known = idxs >= 0
            # Row resolve for every known id in one vectorized search
            # (series_indices is sorted).
            cand = np.searchsorted(blk.series_indices, idxs[known])
            cand = np.minimum(cand, len(blk.series_indices) - 1)
            present = blk.series_indices[cand] == idxs[known]
            rows = cand[present]
            if not len(rows):
                continue
            kpos = np.flatnonzero(known)[present]
            words = np.ascontiguousarray(blk.words[rows])
            charge_read(n_bytes=int(words.nbytes))
            out.append({
                "bs": bs,
                "ids": [ids[int(i)] for i in kpos],
                "words": words,
                "nbits": np.ascontiguousarray(blk.nbits[rows]),
                "npoints": np.ascontiguousarray(blk.npoints[rows]),
                "window": int(blk.window),
                "time_unit": int(blk.time_unit),
            })
        return {"blocks": out}

    # ------------------------------------------------------------------ admin

    def rpc_truncate(self, ns: bytes):
        nsobj = self.db.namespace(ns)
        n = sum(sh.num_series() for sh in nsobj.shards.values())
        shard_ids = list(nsobj.shards)
        for sid in shard_ids:
            nsobj.remove_shard(sid)
            nsobj.assign_shard(sid)
        return n

    def rpc_namespaces(self):
        out = []
        for name, nsobj in list(self.db.namespaces.items()):
            out.append({
                "name": name,
                "retention_ns": nsobj.opts.retention_ns,
                "block_size_ns": nsobj.opts.block_size_ns,
                "index_enabled": nsobj.opts.index_enabled,
                "num_shards": len(nsobj.shards),
            })
        return out


class NodeServer:
    """Threaded TCP listener dispatching wire frames to a NodeService
    (tchannelthrift NewServer + ListenAndServe equivalent)."""

    def __init__(self, service: NodeService, host: str = "127.0.0.1", port: int = 0):
        self.service = service
        svc = self.service

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                sock = self.request
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                try:
                    while True:
                        req = wire.read_dict_frame(sock)
                        msg_id = req.get("id", 0)
                        # Optional deadline budget (ns remaining at send
                        # time) rides the request frame as "d"; re-anchored
                        # on this host's monotonic clock.
                        deadline = wire.deadline_from_frame(req)
                        try:
                            pri = req.get("pri")
                            result, sp = svc.dispatch_traced(
                                req["m"], req.get("a", {}),
                                deadline=deadline,
                                priority_hint=pri if
                                isinstance(pri, str) else None,
                                trace_ctx=wire.trace_from_frame(req),
                                encode=True)
                            resp = {"id": msg_id, "ok": True, "r": result}
                            if sp is not None:
                                # Finished server-side span tree for the
                                # caller to graft (one cross-process tree
                                # per request).
                                resp[wire.SPAN_KEY] = sp
                            wire.write_frame(sock, resp)
                        except DeadlineExceeded as e:
                            # Typed error frame: the caller distinguishes
                            # "server killed it for MY deadline" (stop
                            # waiting, don't retry) from app errors.
                            wire.write_frame(sock, {"id": msg_id, "ok": False,
                                                    "kind": "deadline",
                                                    "err": str(e)})
                        except ResourceExhausted as e:
                            # Typed shed frame: a query limit or the
                            # admission gate rejected this request. The
                            # client classifies it retryable-with-backoff
                            # (the condition clears as windows expire and
                            # in-flight work drains) — the opposite of
                            # "deadline", which never retries.
                            wire.write_frame(sock, {
                                "id": msg_id, "ok": False,
                                "kind": "resource_exhausted", "err": str(e)})
                        # DELIBERATE broad except: the dispatch contract is
                        # to return ANY server-side application error to the
                        # caller as a typed error frame — the wire write in
                        # the try is the success path, and its own failures
                        # hit the outer typed handler when the error frame
                        # write below also fails.
                        except Exception as e:  # noqa: BLE001  # m3lint: disable=broad-except-wire-io
                            wire.write_frame(
                                sock, {"id": msg_id, "ok": False, "err": f"{type(e).__name__}: {e}"}
                            )
                except (ConnectionError, OSError, ValueError):
                    # ValueError = malformed/truncated frame (wire.decode
                    # normalizes every corrupt-buffer case to it): the
                    # stream is desynchronized, so drop the connection —
                    # don't let the handler thread die with a traceback.
                    return

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.address = self._server.server_address
        self._thread: Optional[threading.Thread] = None

    @property
    def endpoint(self) -> str:
        return f"{self.address[0]}:{self.address[1]}"

    def start(self):
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="accept-node-rpc",
            daemon=True)
        self._thread.start()
        return self

    def close(self):
        self._server.shutdown()
        self._server.server_close()
