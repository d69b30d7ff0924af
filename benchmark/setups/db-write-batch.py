"""The set-up's scrapes through the node's batched write,
`Database.write_batch`: straight into the dbnode, past the coordinator.
The fastest way to a store of a given depth (3.1 us a sample); nothing
the ingest path builds (the shard memo, rule matches, aggregated
namespaces) is built by it."""

import numpy as np

from harness import datagen


def load(server, say) -> dict:
    from m3_tpu.metrics import id as metric_id

    tags = datagen.wire_tags(server.labels)
    name = server.cfg["schema"]["measurement"].encode()
    ids = [metric_id.encode(name, {k: v for k, v in t.items()
                                   if k != b"__name__"}) for t in tags]
    n = len(ids)
    db, ns = server.handle.db, server.handle.namespace

    def write_scrape(k, ts, values):
        db.write_batch(ns, ids, np.full(n, ts, np.int64), values,
                       tags if k == 0 else None)

    server.replay_scrapes(write_scrape, say)
    return {"series": n,
            "samples": n * int(server.cell.traffic["setup"]["load_steps"])}
