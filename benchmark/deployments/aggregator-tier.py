"""The aggregation tier as M3 deploys it, every service in this one
process (a chip belongs to one process) and each booted through its own
normal entry point from the configuration's own block:

- `kv`: `services.run_kv`, the cluster's KV service (in memory), on a
  port of its own; every other service is given its endpoint;
- `dbnode`: `services.run_dbnode`, the node with its embedded
  coordinator (`dbnode-restarted`'s handle: it can be restarted in place
  over its own data directory). Its `coordinator` block has the
  namespace list, `downsample.remote_aggregator` (no downsampler is
  embedded: what a rule matches goes to the aggregator placement as
  timed metrics, to every replica) and `ingest.m3msg` (the consumer the
  flushed aggregates come back through);
- `aggregators`: one `services.run_aggregator` an entry, each joining
  the placement (`register_in_placement`) and flushing to the m3msg
  topic (`flush_handler: producer`).

Nothing is wired here: no aggregator, producer, consumer or ingester is
constructed, and no object of one service is handed to another; they
find each other through the KV service, as processes on several hosts
do. EVERY block is hydrated (`services.load_dict`) before anything is
started, so a program that does not know a key of them fails here, in
`boot`, with no thread or socket of a service started.

The handle is `dbnode-restarted`'s (`base`, `db`, `persist`, `writer`,
`restart()`, `close()`), and besides: `namespace` the UNAGGREGATED
namespace (the harness holds it to `setup.sealed_blocks`, and the checks
of the write side read it), `aggregated_namespace` and `resolution_ns`,
`aggregators` {instance id: `AggregatorHandle`}, `kv` the `KVHandle`,
`booted_at`, and `tier_log`: what the program's own spans said of every
flush round that emitted and of every consumed message, kept from boot
by a reporter on the program's tracer (`Tracer.reporters`: the ring
holds 128 roots, a window has thousands) — `flushes` [(instance, role,
the round's first instant, [(window end, windows emitted), ...])] from
the `aggregator.flush` roots, `writes` [(m3msg shard, oldest and newest
stamp of the batch, the instant its ingest span ended)] from the
`coordinator.m3msg.ingest` spans under `msg.consume`. The checks read
who emitted which minute, and when, from there and nowhere else.
`close()` stops the aggregators, then the node, then the KV service."""

import contextlib
import os
import sys
import time

from harness import spec

_restarted = spec.load_part("deployments", "dbnode-restarted")


class TierLog:
    """The tier's flush rounds and ingested messages, from its spans."""

    def __init__(self):
        self.flushes, self.writes = [], []

    def __call__(self, root):
        if root.name == "aggregator.flush":
            self.flushes.append((
                root.tags.get("instance"), root.tags.get("role"),
                root.start_ns, list(root.tags.get("window_ends", ()))))
        elif root.name == "msg.consume":
            for sp in root.children:
                ends = getattr(sp, "tags", {}).get("window_ends")
                if ends and sp.name == "coordinator.m3msg.ingest":
                    self.writes.append((root.tags.get("shard"), ends[0],
                                        ends[1], sp.end_ns))


class Handle(_restarted.Handle):
    def __init__(self, node_cfg: dict, clock, kv, agg_cfgs):
        from m3_tpu.utils import tracing

        self.kv = kv
        self.aggregators = {}
        self.tier_log = TierLog()
        self._tracer = tracing.TRACER
        try:
            # a program without the hook fails here, nothing started
            self._tracer.reporters.append(self.tier_log)
            super().__init__(node_cfg, clock)
            from m3_tpu.services import run_aggregator

            for cfg in agg_cfgs:
                with contextlib.redirect_stdout(sys.stderr):
                    self.aggregators[cfg.instance_id] = run_aggregator(
                        cfg, clock=clock)
        except BaseException:
            self.close()
            raise

    def _start(self, bootstrap: bool):
        from m3_tpu.services import load_dict

        super()._start(bootstrap)
        members = load_dict(dict(self._node_cfg, bootstrap_enabled=bootstrap),
                            "dbnode").coordinator.namespaces
        raw = [m for m in members if not m.aggregated]
        agg = [m for m in members if m.downsample_all]
        if len(raw) != 1 or len(agg) != 1:
            raise SystemExit(
                "benchmark: aggregator-tier takes a coordinator with one "
                "unaggregated namespace and one downsample.all aggregated")
        self.namespace = raw[0].namespace.encode()
        self.aggregated_namespace = agg[0].namespace.encode()
        self.resolution_ns = agg[0].resolution_ns

    def close(self):
        reporters = getattr(self._tracer, "reporters", [])
        if self.tier_log in reporters:
            reporters.remove(self.tier_log)
        for agg in self.aggregators.values():
            agg.close()
        self.aggregators = {}
        if getattr(self, "node", None) is not None:
            self.node.close()
            self.node = None
        if self.kv is not None:
            self.kv.close()
            self.kv = None


def boot(cell, workdir: str, clock) -> Handle:
    from m3_tpu import services

    cfg = cell.config
    node = dict(cfg["dbnode"])
    node["data_dir"] = os.path.join(workdir, "data")
    node["coordinator"] = dict(node.get("coordinator") or {})
    # every block against the program's own schema, before a service starts
    services.load_dict(dict(node, kv_endpoint="127.0.0.1:1"), "dbnode")
    for block in cfg["aggregators"]:
        services.load_dict(dict(block, kv_endpoint="127.0.0.1:1"),
                           "aggregator")
    kv = services.run_kv(services.load_dict(dict(cfg["kv"]), "kv"))
    node["kv_endpoint"] = kv.endpoint
    aggs = [services.load_dict(dict(block, kv_endpoint=kv.endpoint),
                               "aggregator") for block in cfg["aggregators"]]
    handle = Handle(node, clock, kv, aggs)
    handle.booted_at = time.perf_counter()
    return handle
