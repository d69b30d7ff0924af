"""Three replicas holding identical sealed blocks, then an open buffer
written through the cluster.

The scrapes that end up sealed (`load_steps` - `open_steps` of them) go
straight into every node's `Database.write_batch`, the three nodes
loaded CONCURRENTLY (one thread a node, each scrape to all three before
the next): the fastest way to three stores of a given depth, and what a
cluster that has run for an hour holds. Nodes 2 and 3's mediators tick
where `replay_scrapes` ticks node 1's, beside it. The last `open_steps`
scrapes are written through `handle.writer.write_batch` in requests of
`setup.batch_samples` rows with their ids: the coordinator's writer, the
replicating session, one node RPC a host a request, acknowledged at the
configuration's write consistency level. The session is then drained, so
the replica a quorum did not wait for holds its rows too.

Facts beside the usual two: `replica_load_s` (the direct load and the
ticks inside it), `cluster_write_s` and `cluster_write_samples` (the
cluster write path, its drain included)."""

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import datagen


def load(server, say) -> dict:
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.storage.mediator import Mediator

    setup = server.cell.traffic["setup"]
    handle = server.handle
    nodes = handle.nodes
    per = int(setup["batch_samples"])
    steps = int(setup["load_steps"])
    open_from = steps - int(setup["open_steps"])
    tick_at = set(setup.get("tick_at_steps", []))
    tags = datagen.wire_tags(server.labels)
    name = server.cfg["schema"]["measurement"].encode()
    ids = [metric_id.encode(name, {k: v for k, v in t.items()
                                   if k != b"__name__"}) for t in tags]
    n = len(ids)
    ns = handle.namespace
    shards = nodes[0].db.shard_set.lookup_batch(ids)
    others = [Mediator(node.db, node.persist) for node in nodes[1:]]
    pool = ThreadPoolExecutor(max_workers=len(nodes))
    ticking = []
    spent = {"load": 0.0, "cluster": 0.0}
    write_batch = handle.writer.write_batch

    def write_scrape(k, ts, values):
        t0 = time.perf_counter()
        for f in ticking:       # nodes 2.. finish a tick before more rows
            say(f"mediator of another node: {f.result()}")
        del ticking[:]
        if k < open_from:
            col = np.full(n, ts, np.int64)
            first = tags if k == 0 else None
            list(pool.map(lambda node: node.db.write_batch(
                ns, ids, col, values, first, shard_ids=shards), nodes))
            if k in tick_at:    # beside node 1's, which the harness ticks
                ticking.extend(pool.submit(m.run_once) for m in others)
            spent["load"] += time.perf_counter() - t0
            return
        rows = [(t, ts, v) for t, v in zip(tags, values.tolist())]
        for lo in range(0, n, per):
            write_batch(rows[lo:lo + per], series_ids=ids[lo:lo + per])
        if k == steps - 1 and not handle.session.drain(120.0):
            raise RuntimeError("the session did not drain: a replica is "
                               "short of acknowledged rows")
        spent["cluster"] += time.perf_counter() - t0

    try:
        server.replay_scrapes(write_scrape, say)
        t0 = time.perf_counter()
        for stats in pool.map(lambda m: m.run_once(), others):
            say(f"mediator of another node at end of load: {stats}")
        spent["load"] += time.perf_counter() - t0
    finally:
        pool.shutdown(wait=True)
    want = int(setup["sealed_blocks"])
    for node in nodes[1:]:
        nsobj = node.db.namespace(ns)
        sealed = {bs for sh in nsobj.shards.values() for bs in sh.blocks}
        filesets = sum(len(node.persist.list_filesets(ns, sid))
                       for sid in nsobj.shards)
        if len(sealed) != want or filesets < want * len(nsobj.shards):
            raise RuntimeError(
                f"a replica sealed {len(sealed)} block starts and flushed "
                f"{filesets} filesets; the traffic file expects {want} "
                f"blocks of {len(nsobj.shards)} shards on every node")
    return {"series": n, "samples": n * steps,
            "replica_load_s": spent["load"],
            "cluster_write_s": spent["cluster"],
            "cluster_write_samples": n * (steps - open_from)}
