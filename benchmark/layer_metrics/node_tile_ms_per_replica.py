"""Of one replica's part of a clustered read (`node_fetch_ms_per_replica`),
the row resolves and tile gathers — since PR 33 one tile a (block start,
window, unit, width), cut at 4,096 rows, where it was one a (shard, sealed
block); `frame_tiles_per_replica` counts them —: the mean `tile_ns` cost of
the server-side `rpc.fetch_tagged` spans."""

from harness import clusterspans


def read(m):
    return clusterspans.per_replica(m, "tile_ns", 1e6)
