"""storage.block_cache hits over lookups in the window."""

from harness import reduce


def read(m):
    hits = m.moved("storage.block_cache.hits")
    return reduce.share(hits, hits + m.moved("storage.block_cache.misses"))
