"""Top-level database (reference: src/dbnode/storage/database.go `db` +
mediator.go background lifecycle).

Owns namespaces, routes writes by shard hash, appends to the commit log,
and drives the tick -> seal -> flush -> cleanup lifecycle. Background
behavior is explicit (`tick()`, `flush()`) so tests and services control
timing; services wrap it in a mediator thread."""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..persist.diskio import DiskWriteError
from ..utils import tracing
from ..utils.health import DiskHealth, Priority
from ..utils.instrument import ROOT
from ..utils.limits import Backpressure
from ..utils.retry import RetryOptions, Retrier
from ..utils.tracing import clock_ns as _clock
from .buffer import one_block_start
from .namespace import Namespace, NamespaceOptions
from .series import charge_read

_FLUSH_METRICS = ROOT.sub_scope("storage.flush")
# Shard appends of routed batches, and those of them that were *fast*
# (Shard.write_batch: one block, every id known, no tags backfilled);
# each moves once a batch, by the batch's total.
_SHARD_APPENDS = ROOT.counter("storage.write_batch.shard_appends")
_FAST_APPENDS = ROOT.counter("storage.write_batch.fast_appends")


def fold_tags(out: Dict[bytes, set], tags, filter_set, name_only: bool):
    """Fold one series' tags into a CompleteTags accumulator — the single
    definition of filter/name-only semantics shared by the index-backed
    aggregate path and the fetch-derived fallback in query.storage."""
    for k, v in (tags or {}).items():
        if filter_set is not None and k not in filter_set:
            continue
        vals = out.setdefault(k, set())
        if not name_only:
            vals.add(v)


class Database:
    def __init__(self, shard_set, commitlog=None, clock: Callable[[], int] = None,
                 retriever=None, scope=None):
        """shard_set: m3_tpu.sharding.ShardSet; commitlog: persist.CommitLog;
        retriever: storage.retriever.BlockRetriever for disk-backed reads;
        scope: parallel.scope.DeviceScope of a node that owns some of the
        attached devices — the threads that work for this database (its
        RPC handlers, its mediator) enter it; None owns them all."""
        self.scope = scope
        self.shard_set = shard_set
        self.commitlog = commitlog
        self.clock = clock or (lambda: time.time_ns())
        self.retriever = retriever
        self.namespaces: Dict[bytes, Namespace] = {}
        # Guards namespace map mutation (dynamic registry updates arrive on
        # watch threads); iterating code snapshots values() under the GIL.
        self._ns_lock = threading.Lock()
        self._bootstrapped = False
        # Durable-write health: WAL/flush failures degrade the node to a
        # read-only posture (NORMAL/BULK writes shed with Backpressure,
        # CRITICAL and reads keep flowing); the first durable success
        # lifts it. Services register its saturation with the tracker.
        self.disk_health = DiskHealth(trip_after=3)
        # Per-block flush retry: one quick re-attempt absorbs a transient
        # media error; a persistent one surfaces typed, marks the block
        # FAILED (still on the flush schedule) and degrades health.
        self._flush_retrier = Retrier(RetryOptions(
            max_attempts=2, initial_backoff_s=0.02, max_backoff_s=0.1,
            jitter=False))

    # ------------------------------------------------------------- namespaces

    def create_namespace(self, name: bytes, opts: NamespaceOptions = NamespaceOptions(),
                         index=None) -> Namespace:
        with self._ns_lock:
            if name in self.namespaces:
                raise ValueError(f"namespace {name!r} already exists")
            ns = Namespace(name, opts, self.shard_set.all_shard_ids(), index=index,
                           retriever=self.retriever)
            self.namespaces[name] = ns
            return ns

    def ensure_namespace(self, name: bytes,
                         opts: Optional[NamespaceOptions] = None) -> Namespace:
        """Create-if-absent with the standard index wiring — the single
        namespace-creation path shared by config startup, the coordinator
        admin API, and the KV registry watch."""
        existing = self.namespaces.get(name)
        if existing is not None:
            return existing
        opts = opts or NamespaceOptions()
        index = None
        if opts.index_enabled:
            from ..index.namespace_index import NamespaceIndex

            index = NamespaceIndex(opts.index_block_size_ns,
                                   clock=self.clock)
        try:
            return self.create_namespace(name, opts, index=index)
        except ValueError:
            return self.namespaces[name]  # lost a creation race: reuse

    def set_retriever(self, retriever):
        """Attach a disk retriever (serving-path cold reads) to every
        namespace, current and future."""
        self.retriever = retriever
        for ns in list(self.namespaces.values()):
            ns.set_retriever(retriever)

    def drop_namespace(self, name: bytes):
        """Remove a namespace (namespace_watch.go applying a registry
        removal): in-flight reads of the dropped object finish against its
        now-orphaned state; new operations get KeyError. The namespace is
        closed after removal — insert queues drain and its device-block-
        cache residency drops (in-flight reads re-decode; dead-generation
        puts are refused)."""
        with self._ns_lock:
            ns = self.namespaces.pop(name, None)
        if ns is not None:
            ns.close()

    def namespace(self, name: bytes) -> Namespace:
        ns = self.namespaces.get(name)
        if ns is None:
            raise KeyError(f"no such namespace {name!r}")
        return ns

    # ------------------------------------------------------------------ write

    def write(self, namespace: bytes, series_id: bytes, t_ns: int, value: float,
              tags: Optional[dict] = None, priority=None):
        """database.go:536 Write + :561 commit log append."""
        ns = self.namespace(namespace)
        self._check_writable(priority)
        shard_id = self.shard_set.lookup(series_id)
        now = self.clock()
        if priority is None:
            ns.write(shard_id, series_id, t_ns, value, now, tags)
        else:
            ns.shard_for(shard_id).write(series_id, t_ns, value, now, tags,
                                         priority=priority)
        if self.commitlog is not None and ns.opts.writes_to_commitlog:
            try:
                self.commitlog.write(namespace, series_id, t_ns, value, tags)
            except DiskWriteError:
                # WAL append/fsync failure is an ACK failure: the caller
                # sees the typed error, nothing is silently accepted.
                self.disk_health.failure()
                raise
            self.disk_health.success()

    def write_batch(self, namespace: bytes, ids: Sequence[bytes], ts, vals,
                    tags: Optional[Sequence[Optional[dict]]] = None,
                    priority=None, shard_ids: Optional[np.ndarray] = None):
        """database.go:624 WriteBatch: single shard-route + columnar
        append. `priority` (utils.health.Priority) rides down to the
        shard insert queues' admission gates — BULK backfill sheds first
        when a queue's bounded depth fills. `shard_ids`: the rows'
        shards where the caller has routed them already (a serving path
        through ShardSet.lookup_memo); without it the batch is hashed
        here in one lookup_batch, the bulk route. Under a detailed span
        the caller's span receives `buffer_ns` (the shard appends, their
        `lock_wait_ns` inside it) and `commitlog_ns`, once a batch."""
        ns = self.namespace(namespace)
        self._check_writable(priority)
        ts = np.asarray(ts, np.int64)
        vals = np.asarray(vals, np.float64)
        now = self.clock()
        pri = Priority.NORMAL if priority is None else priority
        acc = tracing.detail()  # the caller's span, before ours opens
        # child_span: a real span ONLY under an already-sampled request
        # (the rpc dispatch / executor span) — an untraced write pays one
        # thread-local read (its cost on the chip's host: PERF.md
        # section 6, PR 24).
        with tracing.child_span("storage.write_batch", points=len(ids)):
            if shard_ids is None:
                shard_ids = self.shard_set.lookup_batch(ids)
            self._write_batch_routed(namespace, ns, ids, ts, vals, tags, now,
                                     pri, shard_ids, acc)

    def _write_batch_routed(self, namespace, ns, ids, ts, vals, tags, now,
                            pri, shard_ids, acc):
        """One routing pass for the batch; what has one answer for the
        whole batch is worked out once, before any shard is touched: the
        acceptance window (on the batch's min and max timestamp, so a
        refused batch leaves nothing applied), the block start and the
        reverse-index block (where min and max share one). Each shard then gets contiguous
        slices of the columns in shard order. The sort is stable, so a
        shard's rows keep their arrival order (last arrival wins inside
        a bucket) and the rows applied so far are a prefix of `order`."""
        timed = acc is not None
        t0 = _clock() if timed else 0
        n = len(ids)
        block_start = index_block = None
        if n:
            t_min, t_max = int(ts.min()), int(ts.max())
            past, future = now - ns.opts.buffer_past_ns, now + ns.opts.buffer_future_ns
            if t_min < past or t_max > future:
                bad = int(((ts < past) | (ts > future)).sum())
                raise ValueError(f"{bad} datapoints outside acceptance window")
            block_start = one_block_start(t_min, t_max,
                                          ns.opts.block_size_ns)
            if block_start is not None and ns.index is not None:
                index_block = one_block_start(t_min, t_max,
                                              ns.index.block_size_ns)
        order = np.argsort(shard_ids, kind="stable")
        rows = order.tolist()
        ids_s = list(map(ids.__getitem__, rows))
        ts_s, vals_s = ts[order], vals[order]
        starts, ends, shards = [], [], []
        if n:
            by_shard = shard_ids[order]
            cuts = (np.flatnonzero(by_shard[1:] != by_shard[:-1]) + 1).tolist()
            starts, ends = [0] + cuts, cuts + [n]
            shards = by_shard[starts].tolist()
        row_tags = tags or None
        log = (self.commitlog is not None and ns.opts.writes_to_commitlog)
        appends = fast = 0
        applied = 0  # rows order[:applied] are in their shards' buffers
        try:
            for a, b, sid in zip(starts, ends, shards):
                fast += ns.shard_for(sid).write_batch(
                    ids_s[a:b], ts_s[a:b], vals_s[a:b], now, tags=row_tags,
                    priority=pri, acc=acc, rows=rows[a:b], checked=True,
                    block_start=block_start, index_block=index_block)
                appends += 1
                applied = b
        except BaseException:
            # A later shard's queue shed (Backpressure): earlier shards'
            # writes are already query-visible, so they MUST reach the
            # commit log before the error propagates — otherwise a
            # restart replay silently drops accepted datapoints. They
            # are rows order[:applied], logged in the request's own order.
            if log and applied:
                done = np.sort(order[:applied])
                picked = done.tolist()
                try:
                    self.commitlog.write_batch(
                        namespace, [ids[i] for i in picked], ts[done],
                        vals[done],
                        [row_tags[i] for i in picked]
                        if row_tags is not None else None)
                except DiskWriteError:
                    # The rescue append itself hit the disk fault: the
                    # typed WAL error supersedes the shed — callers must
                    # treat the whole batch as un-acked.
                    self.disk_health.failure()
                    raise
            raise
        finally:
            if appends:
                _SHARD_APPENDS.inc(appends)
                _FAST_APPENDS.inc(fast)
        t1 = _clock() if timed else 0
        if log:
            try:
                self.commitlog.write_batch(namespace, ids, ts, vals, tags)
            except DiskWriteError:
                self.disk_health.failure()
                raise
            self.disk_health.success()
        if timed:
            acc.add_cost("buffer_ns", t1 - t0)
            acc.add_cost("commitlog_ns", _clock() - t1)

    def _check_writable(self, priority) -> None:
        """Read-only posture under persistent disk faults: shed NORMAL
        and BULK writes with typed Backpressure (producers back off, the
        data is never half-accepted) while CRITICAL traffic — health
        probes, replication streams — keeps flowing. Reads are untouched.
        Recovery is automatic: flush retries keep probing the disk and
        the first durable success clears the posture."""
        if priority == Priority.CRITICAL:
            return
        if self.disk_health.read_only():
            raise Backpressure(
                "disk health: durable writes failing, node is read-only "
                "(CRITICAL traffic and reads still flow)")

    # ------------------------------------------------------------------- read

    def read(self, namespace: bytes, series_id: bytes, start_ns: int, end_ns: int):
        """database.go:739 ReadEncoded equivalent, returning decoded
        points. Charges the series/datapoint/bytes query limits
        (query_limits.go): a read that lands inside a query scope bills
        that query's child enforcer; a bare RPC read bills the global
        per-second windows."""
        ns = self.namespace(namespace)
        with tracing.child_span("storage.read") as sp:
            # the read's phases (Shard.read) land on this span as costs
            t, v = ns.read(self.shard_set.lookup(series_id), series_id,
                           start_ns, end_ns, sp if sp.detailed else None)
            sp.set_tag("points", len(t))
        charge_read(n_series=1, n_points=len(t), n_bytes=t.nbytes + v.nbytes)
        return t, v

    def query_ids(self, namespace: bytes, query, start_ns: int = 0, end_ns: int = 2**63 - 1,
                  limit: int = 0):
        """database.go:724 QueryIDs -> reverse index query. `limit`
        pushes the RPC's series cap down to the index (sorted-prefix
        semantics preserved: the index truncates after the sorted union).
        The materialized id count charges the series-fetched limit (the
        index already charged docs-matched per segment pre-gather)."""
        ns = self.namespace(namespace)
        if ns.index is None:
            raise RuntimeError(f"namespace {namespace!r} has no index")
        # The index.query child span lives in NamespaceIndex.query, so
        # direct index callers are traced identically to this path.
        ids = ns.index.query(query, start_ns, end_ns, limit=limit)
        charge_read(n_series=len(ids))
        return ids

    def aggregate_tags(self, namespace: bytes, query, start_ns: int, end_ns: int,
                       name_only: bool = False,
                       filter_names=()) -> "Dict[bytes, set]":
        """database.go AggregateQuery analog: tag name -> distinct values for
        series matching the index query, without touching datapoints. An
        AllQuery answers straight from the index's field/term dictionaries;
        anything else materializes matching IDs and scans registry tags.
        Shared by the node Aggregate RPC and the coordinator's embedded
        CompleteTags path."""
        from ..index import query as iq

        ns = self.namespace(namespace)
        ff = set(filter_names) if filter_names else None
        out: Dict[bytes, set] = {}
        if isinstance(query, iq.AllQuery) and ns.index is not None:
            for name in ns.index.fields(start_ns, end_ns):
                if ff is not None and name not in ff:
                    continue
                out[name] = (set() if name_only else
                             set(ns.index.aggregate_terms(name, start_ns, end_ns)))
            return out
        for sid in self.query_ids(namespace, query, start_ns, end_ns):
            shard = ns.shards.get(self.shard_set.lookup(sid))
            if shard is None:
                continue
            idx = shard.registry.get(sid)
            tags = shard.registry.tags_of(idx) if idx is not None else None
            fold_tags(out, tags, ff, name_only)
        return out

    # -------------------------------------------------------------- lifecycle

    def tick(self, now_ns: Optional[int] = None) -> dict:
        now = now_ns if now_ns is not None else self.clock()
        totals = {"sealed": 0, "expired": 0}
        for ns in list(self.namespaces.values()):
            r = ns.tick(now)
            for k in totals:
                totals[k] += r[k]
        return totals

    def flush(self, persist_manager, now_ns: Optional[int] = None) -> int:
        """Flush all sealed-but-unflushed blocks through a persist manager
        (storage/flush.go); returns number of filesets written."""
        now = now_ns if now_ns is not None else self.clock()
        flushed = 0
        for ns in list(self.namespaces.values()):
            for shard in ns.shards.values():
                wrote = False
                for bs in shard.flushable(now):
                    blk = shard.blocks.get(bs)
                    if blk is None:
                        continue
                    try:
                        self._flush_retrier.attempt(
                            persist_manager.write_block, ns.name,
                            shard.shard_id, blk, shard.registry)
                    except DiskWriteError:
                        # Typed flush failure after the retry budget:
                        # the block stays FAILED (flushable() keeps it
                        # on the schedule), health degrades toward the
                        # read-only posture, and the sweep moves on —
                        # one bad block must not strand the rest.
                        shard.mark_flushed(bs, ok=False)
                        self.disk_health.failure()
                        _FLUSH_METRICS.counter("flush_failed").inc()
                        continue
                    shard.mark_flushed(bs)
                    self.disk_health.success()
                    flushed += 1
                    wrote = True
                if wrote and self.retriever is not None:
                    self.retriever.invalidate(ns.name, shard.shard_id)
            if ns.index is not None:
                # Persist cold index blocks next to the data filesets
                # (persist_manager.go:193-332 index segment persist).
                from ..index import persist as idx_persist

                try:
                    flushed += len(idx_persist.flush_index(
                        persist_manager.root, ns.name, ns.index, now,
                        ns.opts.retention_ns))
                except OSError:
                    # Index segments rebuild from data filesets at
                    # bootstrap: degrade health, count, keep the sweep.
                    self.disk_health.failure()
                    _FLUSH_METRICS.counter("index_flush_failed").inc()
        if self.commitlog is not None and flushed:
            self.commitlog.rotate()
        return flushed

    def evict_flushed(self) -> int:
        """Drop in-memory copies of durably-flushed blocks; reads fall
        through to the retriever. No-op without a retriever (evicting would
        lose the only copy until retention expiry)."""
        if self.retriever is None:
            return 0
        evicted = 0
        for ns in list(self.namespaces.values()):
            for shard in ns.shards.values():
                evicted += shard.evict_flushed()
        return evicted

    def close(self):
        """Shutdown: drain every shard's insert queue (queued writes are
        never stranded by teardown — shard_insert_queue.go Stop)."""
        for ns in list(self.namespaces.values()):
            ns.close()

    def mark_bootstrapped(self):
        self._bootstrapped = True

    @property
    def bootstrapped(self) -> bool:
        return self._bootstrapped
