"""Aggregator client: shard-aware routing of unaggregated metrics to the
aggregator instances owning each metric's shard (reference:
src/aggregator/client/client.go:191-259 WriteUntimedCounter/BatchTimer/Gauge
and the placement-watched shard routing in writer_mgr/queue.go).

Transport is pluggable: the in-process transport calls a local Aggregator
directly (how the coordinator embeds its downsampler); the network transport
sends over the framed-RPC wire (m3_tpu.rpc.wire) like the reference's raw
TCP msgpack/protobuf connections."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..cluster.placement import Placement, ShardState
from ..metrics.metadata import StagedMetadata
from ..metrics.metric import MetricType, MetricUnion
from ..rpc import wire
from ..utils import tracing
from ..utils.hashing import murmur3_32_cached


class AggregatorClient:
    def __init__(self, num_shards: int,
                 placement_getter: Callable[[], Placement],
                 transports: Dict[str, Callable[[MetricUnion, Sequence[StagedMetadata]], bool]]):
        """transports: instance_id -> delivery fn (add_untimed of a local
        Aggregator, or a connection's send); `write_timed_batch` needs a
        transport with `send_body` (aggregator.server.TCPTransport). The
        dict is read at every write: a placement watcher may fill it."""
        self.num_shards = num_shards
        self._placement = placement_getter
        self._transports = transports
        self.dropped = 0
        # (placement, shard -> the ids of its replicas' instances, the
        # one replica set where every shard has the same): read once a
        # placement, not once a sample
        self._owners: Tuple[Optional[Placement], Dict[int, tuple],
                            Optional[tuple]] = (None, {}, None)

    def shard_for(self, metric_id: bytes) -> int:
        return murmur3_32_cached(metric_id) % self.num_shards

    def _instances_for(self, shard: int) -> List[str]:
        p = self._placement()
        return [
            inst.id for inst in p.replicas_for(
                shard, states=(ShardState.INITIALIZING, ShardState.AVAILABLE))
        ]

    def write_untimed(self, mu: MetricUnion,
                      metadatas: Sequence[StagedMetadata] = ()) -> bool:
        """Deliver to every replica of the metric's shard (client.go write:
        one writer per instance owning the shard)."""
        shard = self.shard_for(mu.id)
        delivered = False
        for instance_id in self._instances_for(shard):
            send = self._transports.get(instance_id)
            if send is not None and send(mu, metadatas):
                delivered = True
        if not delivered:
            self.dropped += 1
        return delivered

    def _owners_by_shard(self) -> Tuple[Dict[int, tuple], Optional[tuple]]:
        p = self._placement()
        cached, owners, uniform = self._owners
        if cached is not p:
            owners = {s: tuple(self._instances_for(s))
                      for s in range(self.num_shards)}
            sets = set(owners.values())
            uniform = sets.pop() if len(sets) == 1 else None
            self._owners = (p, owners, uniform)
        return owners, uniform

    def write_timed_batch(self, metric_type: MetricType,
                          ids: Sequence[bytes], times, values, policy,
                          aggregation_id: int = 0) -> int:
        """Timed samples of one (type, policy, aggregation id) class as
        columnar `tbatch` frames (client.go WriteTimed + the queue's
        batching): the rows are cut by the replica set that owns their
        shard, a set's rows are encoded ONCE and the same bytes go to
        each of its instances — the members of a mirrored shard set get
        identical frames. A sample carries its own timestamp, so it joins
        the window that timestamp lies in wherever and whenever it
        arrives. Returns the rows that reached no instance (counted in
        `dropped`). Under a detailed span: costs `encode_ns`, `send_ns`,
        `frames_n`, tag `replicas`."""
        acc = tracing.detail()
        clock = tracing.clock_ns
        owners_of, uniform = self._owners_by_shard()
        groups: Dict[tuple, List[int]] = {}
        if uniform is not None:     # one mirrored shard set: no row is cut
            groups[uniform] = list(range(len(ids)))
        else:
            shard_for = self.shard_for
            for i, mid in enumerate(ids):
                groups.setdefault(owners_of[shard_for(mid)], []).append(i)
        times = np.asarray(times, np.int64)
        values = np.asarray(values, np.float64)
        undelivered = frames = encode_ns = send_ns = 0
        for owners, rows in groups.items():
            t0 = clock() if acc is not None else 0
            whole = len(rows) == len(ids)
            body = wire.encode({
                "t": "tbatch", "mtype": int(metric_type),
                "policy": str(policy), "agg_id": aggregation_id,
                "ids": list(ids) if whole else [ids[i] for i in rows],
                "times": times if whole else times[rows],
                "values": values if whole else values[rows]})
            t1 = clock() if acc is not None else 0
            delivered = False
            for instance_id in owners:
                transport = self._transports.get(instance_id)
                if transport is not None and transport.send_body(body):
                    delivered = True
                    frames += 1
            if not delivered:
                undelivered += len(rows)
            if acc is not None:
                encode_ns += t1 - t0
                send_ns += clock() - t1
        if undelivered:
            self.dropped += undelivered
        if acc is not None:
            acc.add_cost("encode_ns", encode_ns)
            acc.add_cost("send_ns", send_ns)
            acc.add_cost("frames_n", frames)
            acc.set_tag("replicas", max(map(len, groups), default=0))
        return undelivered

    def write_untimed_counter(self, metric_id: bytes, value: int,
                              metadatas: Sequence[StagedMetadata] = ()) -> bool:
        return self.write_untimed(MetricUnion.counter(metric_id, value), metadatas)

    def write_untimed_batch_timer(self, metric_id: bytes, values: Sequence[float],
                                  metadatas: Sequence[StagedMetadata] = ()) -> bool:
        return self.write_untimed(MetricUnion.batch_timer(metric_id, values), metadatas)

    def write_untimed_gauge(self, metric_id: bytes, value: float,
                            metadatas: Sequence[StagedMetadata] = ()) -> bool:
        return self.write_untimed(MetricUnion.gauge(metric_id, value), metadatas)
