"""Seconds the set-up spent making what a node that ran for 16 hours
leaves on its disk, with the program's writers: a device encode a block,
a fileset a shard a block, an index segment an index block.

In `aggns-query-3d`: both namespaces' filesets and index segments."""


def read(m):
    return m.setup.get("fileset_build_s")
