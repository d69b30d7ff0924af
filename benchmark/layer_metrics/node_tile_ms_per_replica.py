"""Of one replica's part of a clustered read (`node_fetch_ms_per_replica`),
the tile gathers, one per (shard, sealed block): the mean `tile_ns` cost of the
server-side `rpc.fetch_tagged` spans."""

from harness import clusterspans


def read(m):
    return clusterspans.per_replica(m, "tile_ns", 1e6)
