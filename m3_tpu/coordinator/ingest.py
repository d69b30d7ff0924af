"""Coordinator ingest: dual-path downsample-and/or-write (reference:
src/cmd/services/m3coordinator/ingest/write.go:78-337
DownsamplerAndWriter — every incoming sample goes to the downsampler
(rule-matched aggregation) and/or directly to unaggregated storage) and
the m3msg ingester (ingest/m3msg/ingest.go) consuming aggregated metrics
published by a standalone aggregator tier."""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

from ..aggregator.handler import decode_aggregated_columns
from ..metrics import id as metric_id
from ..metrics.metric import MetricType
from ..utils import tracing
from ..utils.health import AdmissionGate, Priority
from ..utils.instrument import ROOT
from ..utils.limits import Backpressure
from .downsample import Downsampler
from .promremote import LabelMemo

_scope = ROOT.sub_scope("coordinator.ingest")
_BATCHES = _scope.counter("batches")
_BATCH_SAMPLES = _scope.counter("batch_samples")


class DownsamplerAndWriter:
    """Dual-path writer behind a bounded admission gate: in-flight write
    work past the high watermark sheds bulk backfill first, past capacity
    sheds normal producer traffic too (typed Backpressure — HTTP callers
    get a retryable error, msg-path callers skip the ack so the producer
    redelivers on its exponential backoff schedule), while the aggregated
    pipeline's own output (M3MsgIngester) is never shed."""

    def __init__(self, storage, downsampler: Optional[Downsampler] = None,
                 gate: Optional[AdmissionGate] = None):
        """storage: query-storage-like .write(series_id, tags, t, v)."""
        self._storage = storage
        self._downsampler = downsampler
        # a remote downsampler takes the rows' ids where the caller has
        # them (it sends them on); the embedded one makes its own
        self._ds_takes_ids = getattr(downsampler, "takes_ids", False)
        # Generous-but-finite default: ingest overload protection is on by
        # default; services size it from config where it matters.
        self.gate = gate if gate is not None else AdmissionGate(
            capacity=4096, name="coordinator.ingest")
        # the remote-write decode's memo, kept where ids are made: the
        # ids it yields are the ones write_batch takes as `series_ids`
        self.label_memo = LabelMemo(_series_id)
        self.written = 0
        self.downsampled = 0

    def write(self, tags: Dict[bytes, bytes], t_nanos: int, value: float,
              metric_type: MetricType = MetricType.GAUGE,
              downsample: bool = True, write_unaggregated: bool = True,
              priority: Priority = Priority.NORMAL):
        """write.go WriteBatch dual path. Raises Backpressure when the
        admission gate sheds this priority class."""
        with self.gate.held(priority=priority):
            self._write_admitted(tags, t_nanos, value, metric_type,
                                 downsample, write_unaggregated)

    def _write_admitted(self, tags, t_nanos, value, metric_type,
                        downsample, write_unaggregated):
        if downsample and self._downsampler is not None:
            if self._downsampler.write(tags, t_nanos, value, metric_type):
                self.downsampled += 1
                _scope.counter("downsampled").inc()
        if write_unaggregated:
            self._storage.write(_series_id(tags), tags, t_nanos, value)
            self.written += 1
            _scope.counter("written").inc()

    def write_batch(self, samples: Sequence[tuple],
                    priority: Priority = Priority.NORMAL,
                    series_ids: Optional[Sequence[bytes]] = None, **kw):
        """All-or-nothing admission: the whole batch is admitted ONCE up
        front. Per-sample admission would let a mid-batch shed leave a
        partially-written prefix that the 429-retrying producer then
        re-writes, double-counting it — the same partial-prefix hazard
        m3lint's batch-partial-ingest rule polices at the codec layer.

        Downsampling takes the compiled streaming path: ONE
        Downsampler.write_batch call matches the whole batch against the
        rule set (batch matcher + grouped columnar aggregator adds)
        instead of a per-sample match+append loop; the unaggregated leg
        rides the storage's columnar write_batch when it has one.

        `series_ids`: each row's id where the caller has it already (the
        remote-write handler, from `label_memo`); else made here."""
        samples = list(samples)
        if not samples:
            return
        metric_type = kw.get("metric_type", MetricType.GAUGE)
        downsample = kw.get("downsample", True)
        write_unaggregated = kw.get("write_unaggregated", True)
        with self.gate.held(len(samples), priority=priority):
            if downsample and self._downsampler is not None:
                # a generator: with no rule set installed the
                # downsampler answers before a row of it is built
                rows = ((tags, t, v, metric_type) for tags, t, v in samples)
                matched, dropped = self._downsampler.write_batch(
                    rows, ids=series_ids) if self._ds_takes_ids \
                    else self._downsampler.write_batch(rows)
                # write() counts a sample as downsampled when the
                # downsampler accepted it — DROP_MUST drops included.
                accepted = matched + dropped
                self.downsampled += accepted
                if accepted:
                    _scope.counter("downsampled").inc(accepted)
            if write_unaggregated:
                self._storage_write_batch(samples, series_ids)
        _BATCHES.inc()
        _BATCH_SAMPLES.inc(len(samples))

    def _storage_write_batch(self, samples: Sequence[tuple], sids):
        if sids is None:
            sids = [_series_id(tags) for tags, _t, _v in samples]
        batch_write = getattr(self._storage, "write_batch", None)
        if batch_write is not None:
            batch_write(sids, [s[0] for s in samples],
                        [s[1] for s in samples], [s[2] for s in samples])
        else:
            write = self._storage.write
            for sid, (tags, t_nanos, value) in zip(sids, samples):
                write(sid, tags, t_nanos, value)
        self.written += len(samples)
        _scope.counter("written").inc(len(samples))


class M3MsgIngester:
    """Handler for the m3msg consumer: decodes aggregated metrics published
    by the aggregator tier's ProducerHandler and writes them to storage,
    choosing the namespace for the sample's storage policy
    (ingest/m3msg/ingest.go -> storage write). A consumed message is one
    columnar batch, and goes through the storage's `write_batch` a policy
    (one shard-routed append, one commit-log append), as the embedded
    downsampler's flush does; a storage without one gets a write a row.

    One `coordinator.m3msg.ingest` span a message (a child of the
    consumer's `msg.consume`, so only where the publish was traced): tags
    `namespace`-wise `policy` and `window_ends` (the batch's oldest and
    newest stamp), costs `decode_ns`, `write_ns`, `rows_n` and
    `staleness_ns` — `clock()` at the write's return less the oldest
    window end of the batch: how long after its window closed an
    aggregate became readable. Counter `coordinator.m3msg.rows`."""

    def __init__(self, storage_for_policy: Callable,
                 gate: Optional[AdmissionGate] = None,
                 clock: Optional[Callable[[], int]] = None):
        """storage_for_policy(storage_policy) -> storage with .write(...)
        and, for the batched sink, .write_batch(ids, tags, ts, vals)."""
        self._storage_for = storage_for_policy
        self.gate = gate
        self._clock = clock
        self.ingested = 0
        self._rows = ROOT.counter("coordinator.m3msg.rows")
        # id -> decoded tags (with __name__): a standing series' id is
        # decoded once, not once a minute
        self._tags: Dict[bytes, Dict[bytes, bytes]] = {}

    def _tags_of(self, mid: bytes) -> Dict[bytes, bytes]:
        tags = self._tags.get(mid)
        if tags is None:
            name, tags = metric_id.decode(mid)
            if name:
                tags = {b"__name__": name, **tags}
            if len(self._tags) >= 262144:
                self._tags.clear()
            self._tags[mid] = tags
        return tags

    def __call__(self, shard: int, payload: bytes):
        # CRITICAL priority: this is the aggregation pipeline's own
        # output, already accepted and acked upstream — shedding it here
        # would silently lose aggregated data the platform promised to
        # keep. It is counted against the gate (the depth is honest) but
        # never refused; raw producer traffic sheds first, upstream.
        with tracing.child_span("coordinator.m3msg.ingest") as sp:
            clock = tracing.clock_ns
            t0 = clock() if sp.sampled else 0
            groups = decode_aggregated_columns(payload)
            t1 = clock() if sp.sampled else 0
            n = sum(len(g[1]) for g in groups)
            gate = self.gate
            if gate is not None:
                gate.admit(n, priority=Priority.CRITICAL)
            try:
                oldest = newest = None
                for policy, ids, ts, vs in groups:
                    storage = self._storage_for(policy)
                    if storage is None:
                        continue
                    tags = [self._tags_of(mid) for mid in ids]
                    batch_write = getattr(storage, "write_batch", None)
                    if batch_write is not None:
                        batch_write(ids, tags, ts, vs)
                    else:
                        for row in zip(ids, tags, ts, vs):
                            storage.write(*row)
                    self.ingested += len(ids)
                    self._rows.inc(len(ids))
                    if ts and sp.sampled:
                        sp.set_tag("policy", str(policy))
                        lo, hi = min(ts), max(ts)
                        oldest = lo if oldest is None else min(oldest, lo)
                        newest = hi if newest is None else max(newest, hi)
            finally:
                if gate is not None:
                    gate.release(n)
            if sp.sampled:
                sp.add_cost("decode_ns", t1 - t0)
                sp.add_cost("write_ns", clock() - t1)
                sp.add_cost("rows_n", n)
                if oldest is not None:
                    # the batch's oldest and newest stamp (window ends)
                    sp.set_tag("window_ends", (oldest, newest))
                    if self._clock is not None:
                        sp.add_cost("staleness_ns", self._clock() - oldest)


class RemoteDownsampler:
    """The writer's downsample leg when the aggregation tier is a
    service of its own (`downsample.remote_aggregator`; the reference's
    downsampler built over `remoteAggregator.client`): a batch is
    matched as the embedded downsampler matches it (one
    `Matcher.match_batch` pass, the memoized per-result plan), and what a
    rule sends somewhere goes to the aggregators as TIMED metrics, one
    `tbatch` frame a (storage policy, aggregation id, replica set) —
    each sample with its own timestamp, so it joins the minute that
    timestamp lies in whichever replica, and whenever, it reaches.
    Nothing is aggregated or flushed here: the aggregates come back
    through the m3msg ingester.

    Mapping rules only (policies + aggregation id): a rollup pipeline
    needs untimed adds with staged metadatas, which the timed frame does
    not carry; a match that has one is counted in `unsupported` and its
    sample dropped from this leg."""

    takes_ids = True    # write_batch(rows, ids=...): DownsamplerAndWriter

    def __init__(self, matcher, client, replicas: int = 1,
                 placement_getter: Optional[Callable] = None):
        self._matcher = matcher
        self._client = client
        self._replicas = replicas
        self._placement = placement_getter
        self._ready = replicas <= 0
        self._plans: Dict[int, tuple] = {}
        self.samples_matched = 0
        self.samples_dropped = 0
        self.unsupported = 0
        self._scope = ROOT.sub_scope("coordinator.remote_aggregator")

    def _check_ready(self):
        """Every shard owned by `replicas` instances, once: a coordinator
        that boots before the tier refuses writes (503, retried by the
        sender) rather than feed half a pair."""
        p = self._placement() if self._placement is not None else None
        if p is None or any(len(p.replicas_for(s)) < self._replicas
                            for s in range(p.num_shards)):
            raise Backpressure(
                "remote aggregator: the placement does not hold "
                f"{self._replicas} replica(s) of every shard yet")
        self._ready = True

    def _plan(self, result) -> tuple:
        """(result, drop, ((policy, aggregation id), ...)): where the
        samples of every id that shares this match result go."""
        from .downsample import _must_drop
        from ..aggregator.entry import _active_stage

        metadatas = result.for_existing_id
        if _must_drop(metadatas):
            return (result, True, ())
        targets = []
        active = _active_stage(metadatas, 1 << 62)  # the newest stage
        for pm in (active.metadata.pipelines if active is not None else ()):
            if not pm.pipeline.is_empty():
                self.unsupported += 1
                continue
            targets += [(sp, pm.aggregation_id)
                        for sp in pm.storage_policies]
        if result.for_new_rollup_ids:
            self.unsupported += 1
        return (result, False, tuple(targets))

    def write_batch(self, samples: Sequence[tuple],
                    ids: Optional[Sequence[bytes]] = None):
        """(tags, time_nanos, value, metric_type) rows, as
        `Downsampler.write_batch` (`ids`: each row's id where the caller
        has it); returns (matched, dropped). Opens
        `aggregator.client.write_batch` (costs `match_ns`, `encode_ns`,
        `send_ns`, `samples_n`, `frames_n`; tag `replicas`)."""
        if not self._matcher.has_rules():
            return 0, 0
        if not self._ready:
            self._check_ready()
        with tracing.child_span("aggregator.client.write_batch") as sp:
            clock = tracing.clock_ns
            t0 = clock() if sp.sampled else 0
            samples = list(samples)
            mids = list(ids) if ids is not None else [
                _series_id(tags) for tags, _t, _v, _mt in samples]
            results = self._matcher.match_batch(mids)
            if results is None:
                return 0, 0
            plans = self._plans
            groups: Dict[tuple, tuple] = {}
            dropped = matched = 0
            for i, result in enumerate(results):
                plan = plans.get(id(result))
                if plan is None or plan[0] is not result:
                    if len(plans) >= 262144:
                        plans.clear()
                    plan = plans[id(result)] = self._plan(result)
                if plan[1]:
                    dropped += 1
                    continue
                _tags, t, v, mtype = samples[i]
                for target in plan[2]:
                    g = groups.get((mtype,) + target)
                    if g is None:
                        g = groups[(mtype,) + target] = ([], [], [])
                    g[0].append(mids[i])
                    g[1].append(t)
                    g[2].append(v)
                if plan[2]:
                    matched += 1
            if sp.sampled:
                sp.add_cost("match_ns", clock() - t0)
                sp.add_cost("samples_n", len(samples))
            lost = 0
            for (mtype, policy, agg_id), (ids, ts, vs) in groups.items():
                lost += self._client.write_timed_batch(
                    mtype, ids, ts, vs, policy, agg_id)
            if lost:
                self._scope.counter("undelivered").inc(lost)
            self.samples_matched += matched
            self.samples_dropped += dropped
            return matched, dropped

    def write(self, tags: Dict[bytes, bytes], t_nanos: int, value: float,
              metric_type: MetricType = MetricType.GAUGE) -> bool:
        matched, _dropped = self.write_batch(
            [(tags, t_nanos, value, metric_type)])
        return bool(matched)

    def flush(self, now_nanos: Optional[int] = None) -> int:
        return 0    # the tier flushes itself


def _series_id(tags: Dict[bytes, bytes]) -> bytes:
    name = tags.get(b"__name__", b"")
    return metric_id.encode(name, {k: v for k, v in tags.items()
                                   if k != b"__name__"})
