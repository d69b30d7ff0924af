"""Flush output handlers (reference: src/aggregator/aggregator/handler/ —
blackhole, logging, broadcast, protobuf->m3msg producer handler.go:38).

A handler receives fully-aggregated datapoints (id, timestamp, value,
storage policy). The production path publishes them onto the m3msg-style
sharded pub/sub (m3_tpu.msg) for the coordinator's ingester to consume;
tests use the capture/blackhole handlers."""

from __future__ import annotations

import logging
import threading
from typing import Callable, List, NamedTuple, Optional, Sequence

from ..metrics.policy import StoragePolicy
from ..utils.limits import Backpressure


class AggregatedMetric(NamedTuple):
    id: bytes
    time_nanos: int
    value: float
    storage_policy: StoragePolicy


def _tolist(col):
    return col.tolist() if hasattr(col, "tolist") else list(col)


class Handler:
    def handle(self, metric: AggregatedMetric):  # pragma: no cover - iface
        raise NotImplementedError

    # Adapter so handlers can be passed directly as MetricList flush_fn.
    def __call__(self, metric_id: bytes, time_nanos: int, value: float,
                 storage_policy: StoragePolicy):
        self.handle(AggregatedMetric(metric_id, time_nanos, value, storage_policy))

    def handle_columnar(self, groups):
        """One flush round's emissions as columnar groups of
        (ids, times int64 array, values f64 array, storage_policy) —
        the columnar flush (aggregator/list.py emit_batch) hands the
        WHOLE round in one call so handlers can batch per destination
        (ProducerHandler overrides: one publish per topic shard per
        round). Default: unbatched per-datapoint handle()."""
        for ids, times, values, policy in groups:
            for mid, t, v in zip(ids, _tolist(times), _tolist(values)):
                self.handle(AggregatedMetric(mid, t, v, policy))


class BlackholeHandler(Handler):
    """Drops everything (handler/blackhole.go)."""

    def handle(self, metric: AggregatedMetric):
        pass


class CaptureHandler(Handler):
    """Accumulates flushed metrics in memory — the test sink."""

    def __init__(self):
        self.metrics: List[AggregatedMetric] = []

    def handle(self, metric: AggregatedMetric):
        self.metrics.append(metric)

    def by_id(self, metric_id: bytes) -> List[AggregatedMetric]:
        return [m for m in self.metrics if m.id == metric_id]


class FileHandler(Handler):
    """Appends one durable line per aggregated datapoint:
    `id<TAB>time_nanos<TAB>value<TAB>policy`. Each line is flushed+fsynced
    before handle() returns, so datapoints a leader emitted survive a
    SIGKILL — what lets the failover smoke assert exactly-once flushing
    across a leader crash (the durable analog of handler/logging.go for
    multi-process tests)."""

    def __init__(self, path: str):
        self._f = open(path, "ab", buffering=0)

    def handle(self, metric: AggregatedMetric):
        import os as _os

        self._f.write(b"%s\t%d\t%r\t%s\n" % (
            metric.id, metric.time_nanos, metric.value,
            str(metric.storage_policy).encode()))
        _os.fsync(self._f.fileno())

    def close(self):
        self._f.close()


class LoggingHandler(Handler):
    """handler/logging.go"""

    def __init__(self, logger=None):
        self._log = logger or logging.getLogger("m3_tpu.aggregator.flush")

    def handle(self, metric: AggregatedMetric):
        self._log.info("flush %s@%d=%g (%s)", metric.id, metric.time_nanos,
                       metric.value, metric.storage_policy)


class BroadcastHandler(Handler):
    """Fan out to several handlers (handler/broadcast.go)."""

    def __init__(self, handlers: Sequence[Handler]):
        self._handlers = list(handlers)

    def handle(self, metric: AggregatedMetric):
        for h in self._handlers:
            h.handle(metric)

    def handle_columnar(self, groups):
        for h in self._handlers:
            h.handle_columnar(groups)


class CallbackHandler(Handler):
    """Bridges to an arbitrary callable (used by the coordinator downsampler's
    flush handler, src/cmd/services/m3coordinator/downsample/flush_handler.go)."""

    def __init__(self, fn: Callable[[AggregatedMetric], None]):
        self._fn = fn

    def handle(self, metric: AggregatedMetric):
        self._fn(metric)


class ProducerHandler(Handler):
    """Publishes flushed metrics onto an m3msg producer (handler/protobuf.go:38
    NewProtobufHandler), sharded by metric id the same way the data plane
    shards series. The coordinator's m3msg ingester decodes and writes to
    storage (src/cmd/services/m3coordinator/ingest/m3msg)."""

    def __init__(self, producer, num_shards: int):
        from ..rpc import wire
        from ..utils.hashing import murmur3_32_cached

        self._producer = producer
        self._num_shards = num_shards
        self._encode = wire.encode
        self._hash = murmur3_32_cached
        self.dropped_backpressure = 0
        self.publishes = 0

    def handle(self, metric: AggregatedMetric):
        payload = self._encode({
            "id": metric.id,
            "t": metric.time_nanos,
            "v": metric.value,
            "sp": str(metric.storage_policy),
        })
        try:
            self._producer.publish(
                self._hash(metric.id) % self._num_shards, payload)
            self.publishes += 1
        except Backpressure:
            # The producer buffer is past its watermark: the flush must
            # finish (a wedged flush loses EVERY window, not one metric),
            # so this datapoint is counted as dropped — the same outcome
            # drop-oldest would have forced, surfaced explicitly and
            # earlier, while the buffer still holds undropped history.
            self.dropped_backpressure += 1

    def handle_columnar(self, groups):
        """One flush round batched: rows bucket by topic shard and ship
        as ONE columnar publish per shard per round (ids + raw int64/f64
        columns + per-row policy strings) instead of one encode+publish
        per datapoint. The coordinator ingester decodes either payload
        form via decode_aggregated_batch. Publishes counted in
        `publishes` so tests/smokes can assert the one-publish-per-
        destination contract."""
        import numpy as np

        shards: dict = {}
        nsh = self._num_shards
        h = self._hash
        for ids, times, values, policy in groups:
            sp = str(policy)
            for mid, t, v in zip(ids, _tolist(times), _tolist(values)):
                shards.setdefault(h(mid) % nsh, []).append((mid, t, v, sp))
        for shard, rows in shards.items():
            payload = self._encode({
                "b": 1,
                "ids": [r[0] for r in rows],
                "ts": np.asarray([r[1] for r in rows], np.int64),
                "vs": np.asarray([r[2] for r in rows], np.float64),
                "sps": [r[3] for r in rows],
            })
            try:
                self._producer.publish(shard, payload)
                self.publishes += 1
            except Backpressure:
                # same contract as handle(): the flush must finish; the
                # whole shard batch is counted dropped
                self.dropped_backpressure += len(rows)


class TopicProducerHandler(Handler):
    """`ProducerHandler` over a topic NAMED in configuration and kept in
    KV (handler/protobuf.go's writer is built from the topic service):
    the producer is made when the topic is first seen and made again
    when its consumer services change; each consumer service's placement
    is read from `_placement/<service id>`, watched. Until a topic with a
    consumer service exists a flush has nowhere to go: its rows are
    counted in `dropped_no_topic` (the flush itself must finish)."""

    def __init__(self, store, topic: str, **producer_opts):
        from ..msg.topic import TopicService

        self._store = store
        self._opts = producer_opts
        self._lock = threading.Lock()
        self._inner: Optional[ProducerHandler] = None
        self._producer = None
        self._services: tuple = ()
        self._placements: dict = {}     # service id -> Placement | None
        self._watched: set = set()
        self.dropped_no_topic = 0
        TopicService(store).on_change(topic, self._on_topic)

    def _on_placement(self, service_id: str, value):
        import json

        from ..cluster.placement import Placement

        self._placements[service_id] = Placement.from_json(
            json.loads(value.data.decode()), value.version)

    def _on_topic(self, topic):
        from ..msg.producer import Producer

        with self._lock:
            services = tuple(cs.service_id for cs in topic.consumer_services)
            if services == self._services or not services:
                return
            for sid in services:
                if sid not in self._watched:
                    self._watched.add(sid)
                    self._store.on_change(
                        "_placement/" + sid,
                        lambda _k, v, sid=sid: self._on_placement(sid, v))
            old = self._producer
            self._producer = Producer(
                topic, {sid: (lambda sid=sid: self._placements.get(sid))
                        for sid in services}, **self._opts)
            self._inner = ProducerHandler(self._producer, topic.num_shards)
            self._services = services
        if old is not None:
            old.close()

    @property
    def producer(self):
        return self._producer

    def unacked(self) -> int:
        producer = self._producer
        return producer.unacked() if producer is not None else 0

    def handle(self, metric: AggregatedMetric):
        inner = self._inner
        if inner is None:
            self.dropped_no_topic += 1
        else:
            inner.handle(metric)

    def handle_columnar(self, groups):
        inner = self._inner
        if inner is None:
            self.dropped_no_topic += sum(len(g[0]) for g in groups)
        else:
            inner.handle_columnar(groups)

    def close(self):
        with self._lock:
            producer, self._producer, self._inner = self._producer, None, None
        if producer is not None:
            producer.close()


def decode_aggregated(payload: bytes) -> AggregatedMetric:
    """Inverse of ProducerHandler's encoding, for the coordinator ingester."""
    from ..metrics.policy import StoragePolicy
    from ..rpc import wire

    obj = wire.decode(payload)
    return AggregatedMetric(
        obj["id"], obj["t"], obj["v"], StoragePolicy.parse(obj["sp"]))


def decode_aggregated_columns(payload: bytes) -> list:
    """Either ProducerHandler payload form as columnar groups a storage
    policy: [(policy, ids, times, values)], times and values as lists —
    what a batched sink takes, with no object a row."""
    from ..metrics.policy import StoragePolicy
    from ..rpc import wire

    obj = wire.decode(payload)
    if not obj.get("b"):
        return [(StoragePolicy.parse(obj["sp"]), [obj["id"]], [obj["t"]],
                 [obj["v"]])]
    ids, ts, vs, sps = (obj["ids"], _tolist(obj["ts"]), _tolist(obj["vs"]),
                        obj["sps"])
    if not sps or sps.count(sps[0]) == len(sps):    # one policy: no cut
        return [(StoragePolicy.parse(sps[0]), ids, ts, vs)] if sps else []
    cut: dict = {}
    for i, sp in enumerate(sps):
        cut.setdefault(sp, []).append(i)
    return [(StoragePolicy.parse(sp), [ids[i] for i in rows],
             [ts[i] for i in rows], [vs[i] for i in rows])
            for sp, rows in cut.items()]


def decode_aggregated_batch(payload: bytes) -> List[AggregatedMetric]:
    """Decode either ProducerHandler payload form — one single-metric
    dict (handle) or one columnar shard batch (handle_columnar) — into
    a list of AggregatedMetric."""
    from ..metrics.policy import StoragePolicy
    from ..rpc import wire

    obj = wire.decode(payload)
    if not obj.get("b"):
        return [AggregatedMetric(
            obj["id"], obj["t"], obj["v"], StoragePolicy.parse(obj["sp"]))]
    pols: dict = {}
    out = []
    for mid, t, v, sp in zip(obj["ids"], _tolist(obj["ts"]),
                             _tolist(obj["vs"]), obj["sps"]):
        pol = pols.get(sp)
        if pol is None:
            pol = pols[sp] = StoragePolicy.parse(sp)
        out.append(AggregatedMetric(mid, t, v, pol))
    return out
