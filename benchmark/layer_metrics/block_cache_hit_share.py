"""storage.block_cache hits over lookups in the window.

In `aggns-query-3d` the lookups fall on an aggregated namespace (2.77 GB
of planes) larger than the cache's budget and read over 67 of its 72
hours."""

from harness import reduce


def read(m):
    hits = m.moved("storage.block_cache.hits")
    return reduce.share(hits, hits + m.moved("storage.block_cache.misses"))
