"""Seconds the set-up spent loading the replicas' sealed history straight
into every node's `Database.write_batch`, the nodes side by side, and
ticking the other nodes' mediators."""


def read(m):
    return m.setup.get("replica_load_s")
