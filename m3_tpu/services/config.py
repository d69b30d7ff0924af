"""YAML service configuration (reference: each binary takes a single
'-f config.yml' flag parsed into validated structs via m3x/config,
src/cmd/services/m3dbnode/config/config.go etc.).

Configs are plain dataclasses hydrated from YAML with unknown-key
validation, mirroring the reference's strict unmarshal."""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional

import yaml

from ..query.promql import parse_duration_ns


class ConfigError(ValueError):
    pass


@dataclasses.dataclass
class NamespaceConfig:
    name: str = "default"
    retention: str = "48h"
    block_size: str = "2h"
    index_enabled: bool = True
    # Mutable-buffer acceptance window (bufferPast/bufferFuture in the
    # reference's namespace options); small values let integration
    # drills seal blocks in seconds instead of hours.
    buffer_past: str = "10m"
    buffer_future: str = "2m"
    # The reverse index's block (indexOptions.blockSize in the
    # reference's namespace options); empty: the program's default.
    index_block_size: str = ""

    @property
    def retention_ns(self) -> int:
        return parse_duration_ns(self.retention)

    @property
    def index_block_size_ns(self) -> Optional[int]:
        if not self.index_block_size:
            return None
        return parse_duration_ns(self.index_block_size)

    @property
    def block_size_ns(self) -> int:
        return parse_duration_ns(self.block_size)

    @property
    def buffer_past_ns(self) -> int:
        return parse_duration_ns(self.buffer_past)

    @property
    def buffer_future_ns(self) -> int:
        return parse_duration_ns(self.buffer_future)


@dataclasses.dataclass
class DBNodeConfig:
    host_id: str = "m3db_local"
    listen_address: str = "127.0.0.1:0"
    http_listen_address: str = ""
    data_dir: str = "/tmp/m3_tpu_data"
    num_shards: int = 64
    replication_factor: int = 1
    namespaces: List[NamespaceConfig] = dataclasses.field(
        default_factory=lambda: [NamespaceConfig()])
    commitlog_enabled: bool = True
    # "write_behind" (flush-interval durability) or "write_wait" (every
    # write fsynced before its ack — the zero-acked-loss contract the
    # kill -9 drill asserts; commit_log.go:241 strategies).
    commitlog_strategy: str = "write_behind"
    # Run the bootstrap chain (filesystem -> commitlog) over data_dir on
    # startup instead of starting empty: the cold-restart path. Off by
    # default to preserve the fresh-start embedded uses.
    bootstrap_enabled: bool = False
    # Background mediator cadence (tick -> flush -> snapshot -> cleanup,
    # mediator.go ongoingTick); empty disables the background thread.
    tick_interval: str = ""
    # Background fileset scrub cadence (storage/scrub.py: cold-data row
    # checksum verification + quarantine + repair routing); empty
    # disables the scrubber thread.
    scrub_interval: str = ""
    kv_path: str = ""          # FileStore path; empty = in-memory
    kv_endpoint: str = ""      # networked KV service; overrides kv_path
    coordinator: Optional["CoordinatorConfig"] = None  # embedded mode
    # The devices this node owns, as positions in jax.devices(): its
    # flush mesh, block cache and HBM budget span these alone
    # (parallel/scope.py). Empty: every attached device — a node that
    # has its process, and its host's chips, to itself.
    devices: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DownsampleConfig:
    # Every metric is downsampled into the namespace (the default
    # mapping rule); false: only what a rule set sends there, and the
    # resolver treats the namespace as partial.
    all: bool = True


@dataclasses.dataclass
class ClusterNamespaceConfig:
    """One entry of a coordinator's `namespaces` (the reference's
    `clusters.namespaces`, m3coordinator-cluster-template.yml)."""

    namespace: str = "default"
    type: str = "unaggregated"      # or "aggregated"
    retention: str = "48h"
    resolution: str = ""            # aggregated only
    downsample: Optional[DownsampleConfig] = None   # aggregated only

    @property
    def aggregated(self) -> bool:
        return self.type == "aggregated"

    @property
    def retention_ns(self) -> int:
        return parse_duration_ns(self.retention)

    @property
    def resolution_ns(self) -> int:
        return parse_duration_ns(self.resolution) if self.resolution else 0

    @property
    def downsample_all(self) -> bool:
        return self.aggregated and (self.downsample is None
                                    or self.downsample.all)

    def validate(self):
        if self.type not in ("unaggregated", "aggregated"):
            raise ConfigError(f"namespace {self.namespace!r}: unknown type "
                              f"{self.type!r}")
        if self.aggregated and not self.resolution:
            raise ConfigError(f"aggregated namespace {self.namespace!r} "
                              "needs a resolution")
        if not self.aggregated and (self.resolution
                                    or self.downsample is not None):
            raise ConfigError(f"unaggregated namespace {self.namespace!r} "
                              "takes no resolution and no downsample block")


@dataclasses.dataclass
class RemoteAggregatorConfig:
    """`downsample.remote_aggregator` (the reference's
    `downsample.remoteAggregator.client`): every sample a rule matches
    goes to the m3aggregator placement this names, to every replica of
    its shard, in place of the embedded downsampler."""

    # The aggregator placement in the coordinator's KV store (what each
    # `aggregator` service registers itself in).
    placement_key: str = "_agg_placement"
    # Writes are refused (503, nothing sent) until this many instances
    # own every shard: a coordinator that boots before the tier never
    # feeds one replica of a pair alone.
    replicas: int = 1


@dataclasses.dataclass
class CoordinatorDownsampleConfig:
    remote_aggregator: Optional[RemoteAggregatorConfig] = None


@dataclasses.dataclass
class M3MsgIngestConfig:
    """`ingest.m3msg` (the reference's `ingest.m3msg.server`): the
    consumer of the aggregators' flush topic. At start the coordinator
    makes itself the topic's consumer service in KV (the topic, and the
    service's one-instance placement at `_placement/<consumer_service>`),
    so an aggregator's `producer` flush handler finds it by name."""

    listen_address: str = "127.0.0.1:0"
    topic: str = "aggregated_metrics"
    consumer_service: str = "m3coordinator"
    num_shards: int = 64


@dataclasses.dataclass
class CoordinatorIngestConfig:
    m3msg: Optional[M3MsgIngestConfig] = None


@dataclasses.dataclass
class CoordinatorConfig:
    listen_address: str = "127.0.0.1:0"
    # One unaggregated namespace, read and written; or `namespaces`.
    namespace: str = "default"
    # The cluster namespaces: one `type: unaggregated` and any number of
    # `type: aggregated`, each with its retention (and resolution). The
    # embedded downsampler writes into the aggregated ones, and every
    # query is answered by the namespace(s) its range resolves to
    # (query/storage.py resolve). Empty: `namespace` alone.
    namespaces: List[ClusterNamespaceConfig] = dataclasses.field(
        default_factory=list)
    rules_namespace: str = "default"
    carbon_listen_address: str = ""    # empty = disabled
    remotes: List[str] = dataclasses.field(default_factory=list)
    lookback: str = "5m"
    kv_endpoint: str = ""              # standalone mode: cluster KV service
    placement_key: str = "_placement"  # dbnode placement watched for routing
    # Self-scrape interval (e.g. "10s"): the coordinator's instrument
    # registry written back through its own ingest path each interval
    # (tally-self-reporting analog). Empty disables.
    self_scrape_interval: str = ""
    # The devices this coordinator owns (query meshes, client-side tile
    # decode), as DBNodeConfig.devices. Empty: every attached device,
    # or the node's own when embedded in one.
    devices: List[int] = dataclasses.field(default_factory=list)
    # `downsample.remote_aggregator`: a standalone m3aggregator tier in
    # place of the embedded downsampler; `ingest.m3msg`: the consumer its
    # flushes come back through (services/run.py assembles both).
    downsample: Optional[CoordinatorDownsampleConfig] = None
    ingest: Optional[CoordinatorIngestConfig] = None

    @property
    def remote_aggregator(self) -> Optional[RemoteAggregatorConfig]:
        return self.downsample.remote_aggregator if self.downsample else None

    @property
    def m3msg(self) -> Optional[M3MsgIngestConfig]:
        return self.ingest.m3msg if self.ingest else None

    @property
    def self_scrape_interval_s(self) -> Optional[float]:
        if not self.self_scrape_interval:
            return None
        return parse_duration_ns(self.self_scrape_interval) / 1e9

    def validate(self):
        if not self.namespaces:
            return
        for ns in self.namespaces:
            ns.validate()
        names = [ns.namespace for ns in self.namespaces]
        if len(set(names)) != len(names):
            raise ConfigError(f"coordinator namespaces repeat a name: {names}")
        if sum(1 for ns in self.namespaces if not ns.aggregated) != 1:
            raise ConfigError("coordinator namespaces need exactly one "
                              "of type unaggregated")


@dataclasses.dataclass
class AggregatorConfig:
    instance_id: str = "agg_local"
    listen_address: str = "127.0.0.1:0"
    # HTTP admin sidecar (health/status/resign); empty disables it.
    admin_address: str = ""
    num_shards: int = 64
    shard_set_id: str = "shardset-0"
    election_id: str = "agg-election"
    flush_interval: str = "1s"
    kv_path: str = ""
    kv_endpoint: str = ""
    placement_key: str = ""    # empty = static: own all shards
    topic: str = "aggregated_metrics"
    # Durable per-datapoint flush sink (handler.FileHandler); empty
    # disables. Used by the multi-process failover smoke to observe
    # exactly-once flushing across a leader crash.
    flush_log: str = ""
    # Leader lease TTL: a dead leader's lease expires after this long and a
    # follower's campaign wins (services/leader etcd-session TTL analog).
    election_ttl: str = "10s"
    # A window closes this long after its end (maxAllowedWriteLatency /
    # bufferDurationBeforeShardCutover's part in list.go flushBeforeFn):
    # a timed sample that arrives later than that is dropped and counted.
    buffer_past: str = "0s"
    # Where flushed aggregates go: `producer` publishes them to `topic`
    # over m3msg (handler/protobuf.go), `file` appends them to
    # `flush_log`. Empty: `file` when `flush_log` is set, else whatever
    # the caller of run_aggregator passes.
    flush_handler: str = ""
    # With a placement_key: join the placement at start as a member of
    # `shard_set_id` (created if this is the first instance), owning the
    # shard set's shards at this instance's own listen address. The
    # members of one shard set mirror each other: RF is their number.
    register_in_placement: bool = False

    def validate(self):
        if self.flush_handler not in ("", "producer", "file"):
            raise ConfigError(
                f"aggregator flush_handler {self.flush_handler!r}: one of "
                "producer, file")
        if self.flush_handler == "file" and not self.flush_log:
            raise ConfigError("aggregator flush_handler file needs flush_log")
        if self.register_in_placement and not self.placement_key:
            raise ConfigError(
                "aggregator register_in_placement needs a placement_key")


@dataclasses.dataclass
class CollectorConfig:
    num_shards: int = 64
    rules_namespace: str = "default"
    kv_path: str = ""
    kv_endpoint: str = ""


@dataclasses.dataclass
class KVConfig:
    """Standalone cluster-metadata KV service (the etcd-analog process)."""

    listen_address: str = "127.0.0.1:0"
    kv_path: str = ""          # FileStore durability; empty = in-memory


_SERVICES = {
    "dbnode": DBNodeConfig,
    "coordinator": CoordinatorConfig,
    "aggregator": AggregatorConfig,
    "collector": CollectorConfig,
    "kv": KVConfig,
}


def _hydrate(cls, obj: Dict[str, Any]):
    if obj is None:
        obj = {}
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(obj) - set(fields)
    if unknown:
        raise ConfigError(
            f"unknown config keys for {cls.__name__}: {sorted(unknown)}")
    if cls is CoordinatorConfig and "namespace" in obj and obj.get(
            "namespaces"):
        raise ConfigError("coordinator: give `namespace` or `namespaces`, "
                          "not both")
    kwargs = {}
    for name, value in obj.items():
        nested = _NESTED.get((cls, name))
        if nested is None or value is None:
            kwargs[name] = value
        elif isinstance(value, list):
            kwargs[name] = [_hydrate(nested, v) for v in value]
        else:
            kwargs[name] = _hydrate(nested, value)
    out = cls(**kwargs)
    validate = getattr(out, "validate", None)
    if validate is not None:
        validate()
    return out


# (class, key) -> the dataclass its value (or each item of it) hydrates to
_NESTED = {
    (DBNodeConfig, "namespaces"): NamespaceConfig,
    (DBNodeConfig, "coordinator"): CoordinatorConfig,
    (CoordinatorConfig, "namespaces"): ClusterNamespaceConfig,
    (ClusterNamespaceConfig, "downsample"): DownsampleConfig,
    (CoordinatorConfig, "downsample"): CoordinatorDownsampleConfig,
    (CoordinatorDownsampleConfig, "remote_aggregator"): RemoteAggregatorConfig,
    (CoordinatorConfig, "ingest"): CoordinatorIngestConfig,
    (CoordinatorIngestConfig, "m3msg"): M3MsgIngestConfig,
}


def load_file(path: str, service: str):
    """xconfig.LoadFile equivalent: YAML -> validated config dataclass.
    The file may either be the service config directly or contain a
    top-level key per service (the reference's m3dbnode config embeds a
    'coordinator' section the same way)."""
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    return load_dict(raw, service)


def load_dict(raw: Dict[str, Any], service: str):
    cls = _SERVICES.get(service)
    if cls is None:
        raise ConfigError(f"unknown service {service!r}")
    if service in raw and isinstance(raw[service], dict):
        raw = raw[service]
    return _hydrate(cls, raw)
