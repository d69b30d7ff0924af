"""The aggregator: shard-aware ingestion with placement-watched ownership
(reference: src/aggregator/aggregator/aggregator.go:88 — AddUntimed :167,
AddTimed :189, AddForwarded :208, shardFor :268, placement watch :307;
shard.go aggregatorShard).

Each instance owns the shards the placement assigns it; metric IDs hash to
shards with murmur3 % num_shards (aggregator/sharding/hash.go:89). Each
shard owns its own metric map + lists so flushes and ticks parallelize per
shard; a forwarded-writer loops multi-stage pipeline outputs back into the
aggregation ring (forwarded_writer.go)."""

from __future__ import annotations

import collections
import threading
import time as _time
from typing import Callable, Dict, List, Optional, Sequence

from ..metrics.metadata import ForwardMetadata, StagedMetadata
from ..metrics.metric import MetricType, MetricUnion
from ..metrics.policy import StoragePolicy
from ..utils import instrument, tracing
from ..utils.hashing import murmur3_32_cached
from ..utils.tracing import clock_ns as _span_clock
from .election import ElectionManager
from .entry import MetricMap
from .flush import FlushManager, FlushTimesManager
from .handler import Handler
from .list import MetricLists


class AggregatorShard:
    """One shard's aggregation state (aggregator/shard.go): a metric map over
    its own lists, with cutover/cutoff write gating for placement changes."""

    def __init__(self, shard_id: int, clock: Callable[[], int],
                 rate_limit_per_second: int = 0,
                 default_policies: Sequence[StoragePolicy] = ()):
        self.shard_id = shard_id
        self.lists = MetricLists()
        self.map = MetricMap(self.lists, clock, rate_limit_per_second,
                             default_policies)
        # Writes accepted only within [cutover, cutoff) — shards being handed
        # off stop accepting before they're removed (shard.go SetWriteableRange).
        self.cutover_nanos = 0
        self.cutoff_nanos = 2**63 - 1
        self._clock = clock

    def is_writeable(self) -> bool:
        now = self._clock()
        return self.cutover_nanos <= now < self.cutoff_nanos


class ForwardedWriter:
    """Routes rollup-pipeline outputs to the next aggregation stage
    (forwarded_writer.go): the forwarded ID hashes to a shard, and the
    partial aggregate is delivered to every instance owning that shard in
    the aggregator placement — over the wire when the owner is another
    instance, directly when it is this one. Without routing configuration
    (the embedded single-instance downsampler) everything loops back into
    the local aggregator, which owns all shards."""

    def __init__(self, target: "Aggregator"):
        self._target = target
        self._placement = None      # Callable[[], Placement] | None
        self._transports = {}       # instance_id -> send_forwarded fn
        self._local_id = None
        self.dropped = 0

    def set_routing(self, placement_getter, transports, local_instance_id):
        """transports: instance_id -> either a transport OBJECT exposing
        send_forwarded / send_forwarded_batch (TCPTransport — enables the
        one-frame-per-destination batched forwarding) or a bare
        fn(metric_type, id, t, value, meta) (legacy per-item form)."""
        self._placement = placement_getter
        self._transports = dict(transports)
        self._local_id = local_instance_id

    @staticmethod
    def _send_fn(transport):
        send = getattr(transport, "send_forwarded", None)
        return send if send is not None else transport

    def __call__(self, new_id: bytes, t_nanos: int, value: float,
                 meta: ForwardMetadata, source_id: bytes):
        if self._placement is None:
            self._target.add_forwarded(
                MetricType.GAUGE, new_id, t_nanos, value, meta)
            return
        from ..cluster.placement import ShardState

        shard = self._target.shard_for(new_id)
        delivered = False
        for inst in self._placement().replicas_for(
                shard, states=(ShardState.INITIALIZING, ShardState.AVAILABLE)):
            if inst.id == self._local_id:
                delivered |= self._target.add_forwarded(
                    MetricType.GAUGE, new_id, t_nanos, value, meta)
                continue
            tr = self._transports.get(inst.id)
            if tr is not None and self._send_fn(tr)(
                    MetricType.GAUGE, new_id, t_nanos, value, meta):
                delivered = True
        if not delivered:
            self.dropped += 1

    def forward_batch(self, items):
        """Ship one flush round's rollup forwards batched (the sink
        list.py emit_batch collects instead of per-datapoint forward_fn
        calls). Local deliveries apply directly; remote deliveries
        coalesce into ONE columnar `fbatch` frame per (destination
        instance, forward-meta group) per flush round — the PR 7
        tile-RPC shape — via TCPTransport.send_forwarded_batch. Items
        are (new_id, t_nanos, value, meta, source_id)."""
        if self._placement is None:
            add = self._target.add_forwarded
            for new_id, t_nanos, value, meta, _src in items:
                add(MetricType.GAUGE, new_id, t_nanos, value, meta)
            return
        from ..cluster.placement import ShardState

        states = (ShardState.INITIALIZING, ShardState.AVAILABLE)
        placement = self._placement()
        delivered = [False] * len(items)
        pending: Dict[str, List[int]] = {}
        for i, (new_id, t_nanos, value, meta, _src) in enumerate(items):
            shard = self._target.shard_for(new_id)
            for inst in placement.replicas_for(shard, states=states):
                if inst.id == self._local_id:
                    if self._target.add_forwarded(
                            MetricType.GAUGE, new_id, t_nanos, value, meta):
                        delivered[i] = True
                    continue
                if inst.id in self._transports:
                    pending.setdefault(inst.id, []).append(i)
        for inst_id, idxs in pending.items():
            tr = self._transports[inst_id]
            batch_send = getattr(tr, "send_forwarded_batch", None)
            if batch_send is None:
                send = self._send_fn(tr)
                for i in idxs:
                    new_id, t_nanos, value, meta, _src = items[i]
                    if send(MetricType.GAUGE, new_id, t_nanos, value, meta):
                        delivered[i] = True
                continue
            # one frame per meta group (metas differ only across
            # pipelines/policies, so a flush round is typically one
            # frame per destination)
            groups: Dict[tuple, List[int]] = {}
            for i in idxs:
                meta = items[i][3]
                gk = (meta.aggregation_id, meta.storage_policy,
                      meta.pipeline, meta.num_forwarded_times)
                groups.setdefault(gk, []).append(i)
            for gidx in groups.values():
                if batch_send(MetricType.GAUGE, [items[i] for i in gidx]):
                    for i in gidx:
                        delivered[i] = True
        undelivered = delivered.count(False)
        if undelivered:
            self.dropped += undelivered


class _TimedHandler:
    """A traced round's flush handler, its time told apart from the
    reduction's (emit_batch calls the one inside the other)."""

    def __init__(self, handler):
        self._handler = handler
        self.emit_ns = 0
        if hasattr(handler, "handle_columnar"):
            self.handle_columnar = self._columnar

    def _columnar(self, groups):
        t0 = _span_clock()
        try:
            self._handler.handle_columnar(groups)
        finally:
            self.emit_ns += _span_clock() - t0

    def __call__(self, *row):
        t0 = _span_clock()
        try:
            self._handler(*row)
        finally:
            self.emit_ns += _span_clock() - t0


class Aggregator:
    def __init__(self, num_shards: int = 64,
                 clock: Optional[Callable[[], int]] = None,
                 flush_handler: Optional[Handler] = None,
                 election: Optional[ElectionManager] = None,
                 flush_times: Optional[FlushTimesManager] = None,
                 rate_limit_per_second: int = 0,
                 default_policies: Sequence[StoragePolicy] = (),
                 buffer_past_ns: int = 0, instance_id: str = "",
                 drop_late_timed: bool = False):
        """`drop_late_timed`: a timed sample whose window closed more
        than `buffer_past_ns` ago is dropped and counted
        (`aggregator.add.late_dropped`), as the standalone tier must (its
        window may be flushed already); off, it is staged whatever its
        age, as the embedded uses and the tests of the lists expect."""
        self.num_shards = num_shards
        self.instance_id = instance_id
        self._drop_late = drop_late_timed
        self._clock = clock or (lambda: _time.time_ns())
        self._rate_limit = rate_limit_per_second
        self._default_policies = tuple(default_policies)
        self._shards: Dict[int, AggregatorShard] = {}
        self._owned = set(range(num_shards))
        self._flush_handler = flush_handler
        self._forward = ForwardedWriter(self)
        self._flush_mgrs: Dict[int, FlushManager] = {}
        self._election = election
        self._flush_times = flush_times
        self._buffer_past_ns = buffer_past_ns
        self.writes_for_unowned_shard = 0
        # Accepted forwarded partials (tally counter analog; lets tests and
        # operators await "all N stage-1 partials arrived" instead of racing
        # on first-entry creation). Incremented from concurrent
        # per-connection handler threads — guard the non-atomic += the same
        # way RawTCPServer guards frames/errors.
        self.forwarded_received = 0
        self._stats_lock = threading.Lock()
        self._shards_lock = threading.Lock()
        # One flush round at a time, and a resignation between rounds:
        # a leader that steps down has committed the flush times of
        # everything it emitted before its successor can campaign.
        self._flush_lock = threading.Lock()
        # (type, policy, aggregation id) -> {metric id: (entry, elem)}:
        # the timed batch path's memo of the per-metric path's lookup
        self._timed_elems: Dict[tuple, Dict[bytes, tuple]] = {}
        scope = instrument.ROOT.sub_scope("aggregator")
        by_instance = scope.sub_scope("add", instance=instance_id)
        self._timed_n = by_instance.counter("timed")
        self._late_n = by_instance.counter("late_dropped")
        self._flush_counters: Dict[str, tuple] = {}

    # -- placement ---------------------------------------------------------

    def assign_shards(self, shard_ids: Sequence[int]):
        """React to a placement change (aggregator.go:307 updateShardsWithLock):
        new shards open, removed shards get a cutoff and stop accepting."""
        new = set(shard_ids)
        now = self._clock()
        for sid in new - self._owned:
            if sid in self._shards:
                self._shards[sid].cutoff_nanos = 2**63 - 1
        for sid in self._owned - new:
            if sid in self._shards:
                self._shards[sid].cutoff_nanos = now
        self._owned = new
        self._timed_elems.clear()   # ownership is checked where it fills

    def set_forward_routing(self, placement_getter, transports,
                            local_instance_id):
        """Enable cross-instance forwarded pipelines: rollup outputs are
        routed to the instances owning the forwarded ID's shard
        (forwarded_writer.go; proven end-to-end by the reference's
        multi_server_forwarding_pipeline_test.go)."""
        self._forward.set_routing(placement_getter, transports,
                                  local_instance_id)

    def owned_shards(self) -> List[int]:
        return sorted(self._owned)

    def shard_for(self, metric_id: bytes) -> int:
        """aggregator/sharding/hash.go:89 — murmur3 % num_shards."""
        return murmur3_32_cached(metric_id) % self.num_shards

    def _shard(self, metric_id: bytes) -> Optional[AggregatorShard]:
        sid = self.shard_for(metric_id)
        if sid not in self._owned:
            with self._stats_lock:
                self.writes_for_unowned_shard += 1
            return None
        shard = self._shards.get(sid)
        if shard is None:
            # Check-then-create under the lock: concurrent connection
            # handler threads must not each construct the shard — the loser's
            # writes would land in an orphaned object and never flush.
            with self._shards_lock:
                shard = self._shards.get(sid)
                if shard is None:
                    shard = self._shards[sid] = AggregatorShard(
                        sid, self._clock, self._rate_limit,
                        self._default_policies)
        return shard if shard.is_writeable() else None

    # -- ingest ------------------------------------------------------------

    def add_untimed(self, mu: MetricUnion,
                    metadatas: Sequence[StagedMetadata] = ()) -> bool:
        shard = self._shard(mu.id)
        return shard is not None and shard.map.add_untimed(mu, metadatas)

    def add_untimed_batch(self, mus: Sequence[MetricUnion],
                          metadatas: Sequence[StagedMetadata] = ()
                          ) -> List[bool]:
        """Grouped columnar add: every sample in the batch shares ONE
        staged-metadata list (a (pipeline, policy) class from the batch
        matcher), so the clock read and active-stage resolution are paid
        once for the group instead of per metric (entry.go:446
        activeStagedMetadataWith hoisted out of the hot loop). Returns
        per-sample acceptance, order-aligned with mus."""
        from .entry import _active_stage

        now = self._clock()
        active = _active_stage(metadatas, now)
        out = []
        for mu in mus:
            shard = self._shard(mu.id)
            out.append(shard is not None and shard.map.add_untimed_staged(
                mu, active, now))
        return out

    def ensure_entries(self, pairs) -> None:
        """Pre-create entries for (metric_id, metric_type) pairs in
        order — entry type resolution is first-write-wins, so a batched
        writer passes global sample order here before grouped adds."""
        for mid, mtype in pairs:
            shard = self._shard(mid)
            if shard is not None:
                shard.map.ensure_entry(mid, mtype)

    def add_timed(self, metric_type: MetricType, metric_id: bytes,
                  t_nanos: int, value: float, policy: StoragePolicy,
                  aggregation_id: int = 0) -> bool:
        if self._drop_late and t_nanos < self._closed_before(
                self._clock(), policy.resolution.window_ns):
            self._late_n.inc()
            return False
        shard = self._shard(metric_id)
        ok = shard is not None and shard.map.add_timed(
            metric_type, metric_id, t_nanos, value, policy, aggregation_id)
        if ok:
            self._timed_n.inc()
        return ok

    def _closed_before(self, now: int, res_ns: int) -> int:
        """Every instant before this lies in a window that may have been
        flushed (its end plus `buffer_past` is not after `now`)."""
        return (now - self._buffer_past_ns) // res_ns * res_ns

    def add_timed_batch(self, metric_type: MetricType,
                        ids: Sequence[bytes], times: Sequence[int],
                        values, policy: StoragePolicy,
                        aggregation_id: int = 0) -> int:
        """One (type, policy, aggregation id) column group of timed
        samples (a `tbatch` frame): what `add_timed` does a sample, with
        the clock read, the policy's window and the id -> elem lookup
        paid once a frame and once a series instead. `values` is a
        float64 array; a sample stages a one-element view of it. A
        sample whose window has closed (`buffer_past` past its end) is
        dropped and counted, never staged: its window may have been
        flushed, and a second point for it would not be the window's
        aggregate. Returns the samples dropped late."""
        if self._rate_limit:    # the limiter is the per-metric path's
            add = self.add_timed
            return sum(not add(metric_type, mid, t, float(v), policy,
                               aggregation_id)
                       for mid, t, v in zip(ids, times, values))
        now = self._clock()
        # one compare a sample; far in the past where late samples stay
        closed_before = self._closed_before(
            now, policy.resolution.window_ns) if self._drop_late \
            else -1 << 62
        key = (metric_type, policy, aggregation_id)
        elems = self._timed_elems.get(key)
        if elems is None:
            elems = self._timed_elems.setdefault(key, {})
        late = 0
        for i, mid in enumerate(ids):
            t = times[i]
            if t < closed_before:
                late += 1
                continue
            hit = elems.get(mid)
            if hit is None or hit[1].tombstoned:
                shard = self._shard(mid)
                if shard is None:
                    continue
                hit = elems[mid] = shard.map.timed_elem(
                    metric_type, mid, policy, aggregation_id)
            # the entry's access time, as `Entry.add_timed` keeps it:
            # `tick()` expires an entry by it, and a live series'
            # minute must not be cut in two by a tombstone
            hit[0].last_access_nanos = now
            hit[1]._stage(t, values[i:i + 1])
        self._timed_n.inc(len(ids) - late)
        if late:
            self._late_n.inc(late)
        return late

    def add_forwarded(self, metric_type: MetricType, metric_id: bytes,
                      t_nanos: int, value: float, meta: ForwardMetadata) -> bool:
        shard = self._shard(metric_id)
        ok = shard is not None and shard.map.add_forwarded(
            metric_type, metric_id, t_nanos, value, meta)
        if ok:
            with self._stats_lock:
                self.forwarded_received += 1
        return ok

    # -- flush/tick --------------------------------------------------------

    def _flush_mgr(self, shard: AggregatorShard) -> FlushManager:
        mgr = self._flush_mgrs.get(shard.shard_id)
        if mgr is None:
            if self._election is None or self._flush_times is None:
                raise RuntimeError("aggregator not configured for managed flush")
            mgr = self._flush_mgrs[shard.shard_id] = FlushManager(
                shard.lists, self._election, self._flush_times,
                self._flush_handler, self._forward,
                buffer_past_ns=self._buffer_past_ns, shard_id=shard.shard_id)
        return mgr

    def flush(self, now_nanos: Optional[int] = None) -> int:
        """One flush pass over all owned shards, batched into a single
        columnar reduction: every shard collects into ONE FlushBatch, so
        all aggregation shards reduce in one emit_batch (one mesh-sharded
        device program for the round's quantile ordering). With an
        election manager the leader/follower protocol gates emission, and
        the round's per-shard flush times commit as ONE kv transaction
        (FlushTimesManager.store_many); without one, flush directly (the
        embedded coordinator downsampler runs leaderless,
        downsample/leader_local.go)."""
        with self._flush_lock:
            return self._flush_round(
                self._clock() if now_nanos is None else now_nanos)

    def resign(self):
        """Step down from flush leadership between two rounds (POST
        /resign): the round in progress ends, its flush times are in KV,
        then the lease is given up, so the successor starts exactly one
        window after this instance's last."""
        if self._election is None:
            raise RuntimeError("aggregator is not running an election")
        with self._flush_lock:
            self._election.resign()

    def _role(self) -> str:
        return "leader" if self._election.is_leader() else "follower"

    def _flush_round(self, now: int) -> int:
        from .list import FlushBatch, emit_batch

        t0 = _span_clock()
        batch = FlushBatch()
        commits = []
        with self._shards_lock:  # snapshot: handler threads insert shards
            shards = {sid: self._shards[sid] for sid in sorted(self._shards)}
        if self._election is not None:
            # one campaign and one read of the flush times a round
            self._election.campaign()
            persisted = self._flush_times.get_many(list(shards))
        for sid, shard in shards.items():
            if self._election is not None:
                _, commit = self._flush_mgr(shard).plan_into(
                    now, batch, persisted[sid])
                commits.append(commit)
            else:
                for lst in shard.lists.lists():
                    res = lst.resolution_ns
                    target = (now - self._buffer_past_ns) // res * res
                    lst.collect_into(target, batch)
        if self._election is None:
            # the embedded, leaderless downsampler: its own flush span
            # and counters are the coordinator's (`downsample.flush`)
            return emit_batch(batch, self._flush_handler, self._forward)
        windows = len(batch)
        if not windows:
            # a round that closes nothing: the followers' discards and
            # the (unchanged) flush times still commit, no span opens
            self._commit_flush_times(commits)
            return 0
        role = self._role()
        # (backdated to the round's first instant: the collect pass ran
        # before it was known that the round emits)
        with tracing.background_span("aggregator.flush", start_ns=t0,
                                     instance=self.instance_id, role=role,
                                     windows=windows) as sp:
            t1 = _span_clock()
            handler = self._flush_handler
            timed = _TimedHandler(handler) \
                if sp.sampled and handler is not None else handler
            total = emit_batch(batch, timed, self._forward)
            t2 = _span_clock()
            self._commit_flush_times(commits)
            if sp.sampled:
                t3 = _span_clock()
                emit_ns = getattr(timed, "emit_ns", 0)
                sp.add_cost("collect_ns", t1 - t0)
                sp.add_cost("reduce_ns", t2 - t1 - emit_ns)
                sp.add_cost("emit_ns", emit_ns)
                sp.add_cost("flush_times_ns", t3 - t2)
                sp.add_cost("rows_n", total)
                # which windows left this instance in this round, by
                # their END: [(end, windows), ...]
                ends: Dict[int, int] = {}
                for cls, rows in batch.classes.items():
                    for start, n in collections.Counter(rows.starts).items():
                        end = start + cls.res_ns
                        ends[end] = ends.get(end, 0) + n
                sp.set_tag("window_ends", sorted(ends.items()))
        counters = self._flush_counters.get(role)
        if counters is None:
            by = instrument.ROOT.sub_scope(
                "aggregator.flush", instance=self.instance_id, role=role)
            counters = self._flush_counters[role] = (
                by.counter("windows"), by.counter("rows"))
        counters[0].inc(windows)
        counters[1].inc(total)
        return total

    def _commit_flush_times(self, commits):
        if not commits:
            return
        pending: Dict[int, Dict[int, int]] = {}
        for commit in commits:
            commit(pending)
        if pending:
            self._flush_times.store_many(pending)

    def tick(self) -> int:
        """Expire idle entries across shards (aggregator.go tickInternal)."""
        with self._shards_lock:
            shards = list(self._shards.values())
        expired = sum(s.map.tick() for s in shards)
        if expired:
            # the timed batch path's memo lets go of what expired (a hit
            # on a tombstoned elem looks the series up again; one never
            # hit again would stay for good)
            for key, elems in list(self._timed_elems.items()):
                self._timed_elems[key] = {
                    mid: hit for mid, hit in list(elems.items())
                    if not hit[1].tombstoned}
        return expired

    def num_entries(self) -> int:
        with self._shards_lock:
            shards = list(self._shards.values())
        return sum(len(s.map) for s in shards)
