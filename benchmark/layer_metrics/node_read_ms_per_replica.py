"""Of one replica's part of a clustered read (`node_fetch_ms_per_replica`),
the per-series identity and buffer reads (ROADMAP A2's loop): the mean `read_ns` cost of the
server-side `rpc.fetch_tagged` spans."""

from harness import clusterspans


def read(m):
    return clusterspans.per_replica(m, "read_ns", 1e6)
