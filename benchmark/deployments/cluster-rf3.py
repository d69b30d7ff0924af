"""M3DB's own cluster in one process: the configuration's `cluster.nodes`
dbnodes (`services.run_dbnode` each, over the configuration's `dbnode`
block), a placement of `dbnode.num_shards` shards x `replication_factor`
over them, one isolation group each, written to the KV the way
`cluster/placement.py` writes it, a replicating client `Session` over
the placement-watched topology at the configuration's consistency
levels, and a dedicated coordinator over that session
(`services.run_coordinator(cfg, session=...)`): every read and write of
the coordinator crosses the node RPC. One process, because the
benchmark's process owns the chips; each service is given the devices
`cluster.devices` names (a `devices` key in its configuration), so the
coordinator's decode runs on one chip and each node's buffer, block
cache and seal/encode on another.

A program that has no device scopes, or whose cluster storage has no
columnar write, cannot run this deployment: `boot` fails at once, before
anything is started."""

import os


class Handle:
    """What the harness takes from a booted deployment (`base`, `db`,
    `persist`, `writer`, `namespace`, `close()`: `db` and `persist` are
    node 1's, whose mediator the harness ticks), and what the cluster's
    own set-up and check take besides: `nodes` (all of them, in device
    order), `session`, `coordinator`, `node_devices`,
    `coordinator_devices` (positions in jax.devices())."""

    def __init__(self, nodes, session, coordinator, namespace: bytes,
                 node_devices, coordinator_devices):
        self.nodes, self.session = nodes, session
        self.coordinator = coordinator
        self.base = coordinator.endpoint
        self.writer = coordinator.writer
        self.namespace = namespace
        self.db, self.persist = nodes[0].db, nodes[0].persist
        self.node_devices = node_devices
        self.coordinator_devices = coordinator_devices

    def close(self):
        self.coordinator.close()
        self.session.close()
        for node in self.nodes:
            node.close()


def boot(cell, workdir: str, clock) -> Handle:
    from m3_tpu.parallel import scope  # noqa: F401 - no scopes, no cluster in one process
    from m3_tpu.query.storage import SessionStorage

    if not hasattr(SessionStorage, "write_batch"):
        raise RuntimeError("this program's SessionStorage has no write_batch: "
                           "the set-up's cluster write would take hours")
    import jax

    from m3_tpu.client.decode import ConflictStrategy
    from m3_tpu.client.session import Session, SessionOptions
    from m3_tpu.cluster import kv as cluster_kv
    from m3_tpu.cluster.placement import Instance, PlacementService
    from m3_tpu.cluster.topology import (ConsistencyLevel, DynamicTopology,
                                         ReadConsistencyLevel)
    from m3_tpu.services import load_dict, run_coordinator, run_dbnode

    cfg, cl = cell.config, cell.config["cluster"]
    ndev = len(jax.devices())
    want = max(d for devs in [cl["devices"]["coordinator"]]
               + cl["devices"]["nodes"] for d in devs) + 1
    if ndev < want:
        raise RuntimeError(f"{ndev} device(s) attached, the layout names "
                           f"{want}: every service needs its own")
    nodes, node_devices = [], []
    for i in range(int(cl["nodes"])):
        node = dict(cfg["dbnode"])
        node["host_id"] = "node%d" % (i + 1)
        # node 1's directory is the one the harness sizes (`data`)
        node["data_dir"] = os.path.join(workdir,
                                        "data" if i == 0 else "data%d" % (i + 1))
        node["devices"] = list(cl["devices"]["nodes"][i])
        node_devices.append(node["devices"])
        nodes.append(run_dbnode(load_dict(node, "dbnode"), clock=clock))
    kv = cluster_kv.MemStore()
    placement = PlacementService(kv, cl.get("placement_key", "_placement"))
    placement.init(
        [Instance(n.server.service.host_id, n.endpoint,
                  isolation_group="group%d" % (i % int(cl["isolation_groups"])))
         for i, n in enumerate(nodes)],
        int(cfg["dbnode"]["num_shards"]), int(cl["replication_factor"]))
    session = Session(DynamicTopology(placement), SessionOptions(
        write_consistency=ConsistencyLevel(cl["write_consistency"]),
        read_consistency=ReadConsistencyLevel(cl["read_consistency"]),
        conflict_strategy=ConflictStrategy(cl["conflict_strategy"])))
    coord = dict(cfg.get("coordinator") or {})
    coord["devices"] = list(cl["devices"]["coordinator"])
    ccfg = load_dict(coord, "coordinator")
    coordinator = run_coordinator(ccfg, session=session, kv_store=kv,
                                  clock=clock)
    return Handle(nodes, session, coordinator, ccfg.namespace.encode(),
                  node_devices, coord["devices"])
