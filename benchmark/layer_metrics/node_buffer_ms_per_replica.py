"""Of one replica's part of a clustered read, the `ShardBuffer.read` loops
alone (ROADMAP A2 / A10(a)): the mean `buffer_ns` cost of the server-side
`rpc.fetch_tagged` spans, a stretch inside `node_read_ms_per_replica`'s
`read_ns` (which also holds the routing and the identities)."""

from harness import clusterspans


def read(m):
    return clusterspans.per_replica(m, "buffer_ns", 1e6)
