"""Tiles in the frame one replica builds for one query: the mean `tiles_n`
cost of the server-side `rpc.fetch_tagged` spans. One a block start from
PR 33 (three at the cell's depth); a program whose spans carry no such
cost, which made one per (shard, sealed block), reads nothing."""

from harness import clusterspans


def read(m):
    return clusterspans.per_replica(m, "tiles_n")
