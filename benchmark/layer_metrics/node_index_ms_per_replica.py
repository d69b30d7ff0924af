"""Of one replica's part of a clustered read (`node_fetch_ms_per_replica`),
the index query (tag query to series ids): the mean `index_ns` cost of the
server-side `rpc.fetch_tagged` spans."""

from harness import clusterspans


def read(m):
    return clusterspans.per_replica(m, "index_ns", 1e6)
