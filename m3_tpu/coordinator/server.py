"""Coordinator server assembly (reference: src/query/server/server.go:115
Run — wires storage backend, downsampler, engine, and the HTTP handler).

run_embedded() builds the whole read+write coordinator over an in-process
database (the m3dbnode embedded-coordinator mode, cmd/services/m3dbnode/
main.go:69); run_clustered() goes through the replicating client session."""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Callable, Dict, Optional, Sequence

from ..cluster import kv as cluster_kv
from ..metrics.filters import MATCH_ALL
from ..metrics.matcher import Matcher, RuleSetStore
from ..metrics.policy import Resolution, StoragePolicy
from ..metrics.rules import MappingRuleSnapshot, Rule
from ..parallel import scope as dscope
from ..query import (Engine, LocalStorage, NamespaceAttrs, ResolvingStorage,
                     SessionStorage)
from ..utils import instrument
from .admin import AdminAPI
from .downsample import Downsampler
from .http_api import HTTPApi
from .ingest import DownsamplerAndWriter, RemoteDownsampler
from .rules_engine import RulesEngine
from .selfscrape import SelfScraper

_LOG = logging.getLogger(__name__)


@dataclasses.dataclass
class Coordinator:
    engine: Engine
    writer: DownsamplerAndWriter
    api: HTTPApi
    downsampler: Optional[Downsampler]
    admin: AdminAPI
    # Self-scrape loop (instrument snapshot -> own ingest path) when the
    # deployment enables it; tests/smokes drive scrape_once() directly.
    self_scraper: Optional[SelfScraper] = None
    clock: Optional[object] = None
    _flush_stop: Optional[threading.Event] = None
    _flush_thread: Optional[threading.Thread] = None
    # `downsample.remote_aggregator`: the client of the aggregator tier
    # (its placement watch and connections); `ingest.m3msg`: the consumer
    # the tier's flushes come back through
    aggregator_client: Optional["_RemoteAggregator"] = None
    m3msg_consumer: Optional[object] = None

    @property
    def endpoint(self) -> str:
        return self.api.endpoint

    def flush_downsampler(self, now_nanos: Optional[int] = None) -> int:
        return self.downsampler.flush(now_nanos) if self.downsampler else 0

    def start_downsample_flush(self):
        """The embedded downsampler's flush on a cadence (the reference's
        downsampler flushes itself; a second is the standalone
        aggregator's default `flush_interval`): what a closed window
        holds reaches its aggregated namespace within a second of the
        coordinator's clock passing the window's end. Started by the
        services for a coordinator whose namespace list has aggregated
        namespaces; stopped by `close`. A round whose sink fails is
        counted (`coordinator.downsample.flush_errors`) and logged, and
        its rows are the next round's (`Downsampler._held`)."""
        if not isinstance(self.downsampler, Downsampler) \
                or self._flush_thread is not None:
            return      # a remote tier flushes itself
        stop = self._flush_stop = threading.Event()
        errors = instrument.ROOT.counter("coordinator.downsample.flush_errors")

        def loop():
            while not stop.wait(1.0):
                try:
                    self.downsampler.flush()
                except Exception:   # the loop outlives a failing sink
                    errors.inc()
                    _LOG.exception("downsample flush")

        self._flush_thread = threading.Thread(
            target=loop, name="downsample-flush", daemon=True)
        self._flush_thread.start()

    def rules_engine(self, **kw) -> RulesEngine:
        """Standing recording/alert rules over this coordinator: PromQL
        evaluates through the shared engine (plan cache included) and
        outputs write back through the downsample-and-write path, so
        recorded series are rule-matched AND queryable over HTTP."""
        kw.setdefault("clock", self.clock)
        return RulesEngine(self.engine, self.writer.write_batch, **kw)

    def close(self):
        if self._flush_thread is not None:
            self._flush_stop.set()
            self._flush_thread.join()
        if self.self_scraper is not None:
            self.self_scraper.stop()
        self.api.close()
        if self.m3msg_consumer is not None:
            self.m3msg_consumer.close()
        if self.aggregator_client is not None:
            self.aggregator_client.close()


class _RemoteAggregator:
    """The coordinator's side of `downsample.remote_aggregator`: the
    aggregator placement in KV, watched, a connection an instance it
    names, and the `AggregatorClient` that routes over both."""

    def __init__(self, kv_store, placement_key: str):
        from ..aggregator.client import AggregatorClient

        self.placement = None
        self.transports: Dict[str, object] = {}
        self._kv, self._key = kv_store, placement_key
        self.client = AggregatorClient(1, lambda: self.placement,
                                       self.transports)
        kv_store.on_change(placement_key, self._on_placement)

    def _on_placement(self, _key, value):
        import json

        from ..aggregator.server import TCPTransport
        from ..cluster.placement import Placement

        p = Placement.from_json(json.loads(value.data.decode()),
                                value.version)
        for iid, inst in p.instances.items():
            tr = self.transports.get(iid)
            if tr is not None and tr._endpoint != inst.endpoint:
                tr.close()
                tr = None
            if tr is None:
                self.transports[iid] = TCPTransport(inst.endpoint)
        for iid in set(self.transports) - set(p.instances):
            self.transports.pop(iid).close()
        self.client.num_shards = p.num_shards
        self.placement = p

    def close(self):
        self._kv.off_change(self._key, self._on_placement)
        for tr in list(self.transports.values()):
            tr.close()


def _start_m3msg(cfg, kv_store, aggregated_storages, clock):
    """`ingest.m3msg`: the consumer, its ingester, and this coordinator
    made the topic's consumer service in KV (the topic, created or
    joined, and the service's placement: this one instance, every
    shard), so an aggregator's `producer` handler finds it by name."""
    import json

    from ..cluster.placement import (Instance, Placement, ShardAssignment,
                                     ShardState)
    from ..msg.consumer import Consumer
    from ..msg.topic import ConsumerService, Topic, TopicService
    from .ingest import M3MsgIngester

    ingester = M3MsgIngester(aggregated_storages.get, clock=clock)
    host, _, port = cfg.listen_address.rpartition(":")
    consumer = Consumer(ingester, host=host or "127.0.0.1",
                        port=int(port or 0)).start()
    inst = Instance(cfg.consumer_service, consumer.endpoint, shards={
        s: ShardAssignment(s, ShardState.AVAILABLE)
        for s in range(cfg.num_shards)})
    kv_store.set("_placement/" + cfg.consumer_service, json.dumps(
        Placement({inst.id: inst}, cfg.num_shards, 1).to_json()).encode())
    topics = TopicService(kv_store)
    topic = topics.get(cfg.topic) or Topic(cfg.topic, cfg.num_shards)
    if cfg.consumer_service not in {c.service_id
                                    for c in topic.consumer_services}:
        topics.upsert(topic.add_consumer(ConsumerService(cfg.consumer_service)))
    return consumer


def _policy_of(attrs: NamespaceAttrs) -> StoragePolicy:
    return StoragePolicy(Resolution(attrs.resolution_ns), attrs.retention_ns)


def _storages(make_store: Callable[[bytes], object], namespace: bytes,
              cluster_namespaces: Optional[Sequence[NamespaceAttrs]], clock):
    """(the engine's and the writer's storage, the downsampler's targets
    by policy, the policies every metric is downsampled to). With a
    namespace list (the reference's `clusters.namespaces`) both halves
    come from it: each aggregated namespace is the target of its
    resolution:retention policy, a `downsample.all` one of the default
    mapping rule too, and reads resolve over all of them. One namespace:
    the member itself, no resolver in the path."""
    if not cluster_namespaces:
        return make_store(namespace), {}, ()
    members = [(a, make_store(a.name)) for a in cluster_namespaces]
    agg = {_policy_of(a): store for a, store in members if a.aggregated}
    auto = tuple(_policy_of(a) for a, _s in members
                 if a.aggregated and a.complete)
    if len(members) == 1:
        return members[0][1], agg, auto
    return ResolvingStorage(members, clock), agg, auto


def _build(storage, aggregated_storages: Dict[StoragePolicy, object],
           kv_store: Optional[cluster_kv.MemStore],
           rules_namespace: bytes, clock, create_namespace,
           listen=("127.0.0.1", 0),
           self_scrape_interval_s: Optional[float] = None,
           device_scope=None,
           downsample_all: Sequence[StoragePolicy] = (),
           remote_aggregator=None, m3msg=None) -> Coordinator:
    """`device_scope` (parallel/scope.py): the devices this coordinator
    owns — its engine's query mesh is built over them and its HTTP
    handler threads work inside it; None owns every attached device.
    `downsample_all`: the policies of the `downsample.all` namespaces,
    installed as the default mapping rule (every metric, the metric
    type's default aggregation — `last` for a gauge) beside whatever
    rule set the KV store holds. `remote_aggregator` (placement_key,
    replicas): what the rules match goes to that m3aggregator placement
    as timed metrics and no downsampler is embedded; `m3msg`
    (listen_address, topic, consumer_service, num_shards): the consumer
    that writes the tier's flushes into the aggregated namespaces. Both
    live in `kv_store`."""
    downsampler = None
    remote = consumer = None
    if (remote_aggregator is not None or m3msg is not None) \
            and kv_store is None:
        raise ValueError("a remote aggregator and an m3msg ingester need "
                         "the cluster's KV store")
    if m3msg is not None:
        consumer = _start_m3msg(m3msg, kv_store, aggregated_storages,
                                clock)
    if kv_store is not None or downsample_all:
        auto = ()
        if downsample_all:
            auto = (Rule([MappingRuleSnapshot(
                "downsample-all", 0, MATCH_ALL,
                storage_policies=tuple(downsample_all))]),)
        matcher = Matcher(
            RuleSetStore(kv_store if kv_store is not None
                         else cluster_kv.MemStore()),
            rules_namespace, clock=clock, auto_mapping_rules=auto)

        def write_aggregated(mid, tags, t_ns, value, policy):
            target = aggregated_storages.get(policy, storage)
            target.write(mid, tags, t_ns, value)

        def write_aggregated_batch(rows):
            # one storage write_batch per policy group of the columnar
            # flush (rows: (mid, tags, t_ns, value, policy))
            by_policy: Dict[object, list] = {}
            for row in rows:
                by_policy.setdefault(row[4], []).append(row)
            for policy, group in by_policy.items():
                target = aggregated_storages.get(policy, storage)
                batch_write = getattr(target, "write_batch", None)
                if batch_write is not None:
                    batch_write([r[0] for r in group], [r[1] for r in group],
                                [r[2] for r in group], [r[3] for r in group])
                else:
                    for mid, tags, t_ns, value, _pol in group:
                        target.write(mid, tags, t_ns, value)

        if remote_aggregator is not None:
            remote = _RemoteAggregator(kv_store,
                                       remote_aggregator.placement_key)
            downsampler = RemoteDownsampler(
                matcher, remote.client, remote_aggregator.replicas,
                lambda: remote.placement)
        else:
            downsampler = Downsampler(
                matcher, write_aggregated, clock=clock,
                write_aggregated_batch=write_aggregated_batch)
    writer = DownsamplerAndWriter(storage, downsampler)
    with dscope.entered(device_scope):
        engine = Engine(storage)
    admin = AdminAPI(kv_store if kv_store is not None else cluster_kv.MemStore(),
                     create_namespace=create_namespace)
    api = HTTPApi(engine, writer, admin=admin)
    api.device_scope = device_scope
    api.serve(*listen)
    scraper = None
    if self_scrape_interval_s is not None:
        # Dogfooding like the reference: the coordinator's own instrument
        # registry scraped back through its ingest path.
        scraper = SelfScraper(writer, clock=clock,
                              interval_s=self_scrape_interval_s).start()
    return Coordinator(engine, writer, api, downsampler, admin, scraper,
                       clock=clock, aggregator_client=remote,
                       m3msg_consumer=consumer)


def run_embedded(db, namespace: bytes = b"default",
                 kv_store: Optional[cluster_kv.MemStore] = None,
                 rules_namespace: bytes = b"default",
                 clock=None, listen=("127.0.0.1", 0),
                 create_namespace=None,
                 self_scrape_interval_s: Optional[float] = None,
                 device_scope=None,
                 cluster_namespaces: Optional[Sequence[NamespaceAttrs]] = None,
                 remote_aggregator=None, m3msg=None) -> Coordinator:
    """`cluster_namespaces`: the coordinator's namespace list
    (`_storages`); given, it stands for `namespace`. `remote_aggregator`
    and `m3msg`: `_build`'s."""
    storage, agg, auto = _storages(
        lambda ns: LocalStorage(db, ns), namespace, cluster_namespaces, clock)

    if create_namespace is None:
        def create_namespace(name: bytes, retention_ns: int):
            from ..storage.namespace import NamespaceOptions

            db.ensure_namespace(
                name, NamespaceOptions(retention_ns=retention_ns))

    return _build(storage, agg, kv_store, rules_namespace, clock,
                  create_namespace, listen,
                  self_scrape_interval_s=self_scrape_interval_s,
                  device_scope=device_scope, downsample_all=auto,
                  remote_aggregator=remote_aggregator, m3msg=m3msg)


def run_clustered(session, namespace: bytes = b"default",
                  kv_store: Optional[cluster_kv.MemStore] = None,
                  rules_namespace: bytes = b"default",
                  clock=None, listen=("127.0.0.1", 0),
                  self_scrape_interval_s: Optional[float] = None,
                  device_scope=None,
                  cluster_namespaces: Optional[Sequence[NamespaceAttrs]] = None,
                  remote_aggregator=None, m3msg=None) -> Coordinator:
    storage, agg, auto = _storages(
        lambda ns: SessionStorage(session, ns), namespace,
        cluster_namespaces, clock)
    return _build(storage, agg, kv_store, rules_namespace, clock, None,
                  listen, self_scrape_interval_s=self_scrape_interval_s,
                  device_scope=device_scope, downsample_all=auto,
                  remote_aggregator=remote_aggregator, m3msg=m3msg)
