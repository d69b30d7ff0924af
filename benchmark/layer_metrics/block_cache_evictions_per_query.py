"""Block-cache entries evicted per request of the window
(`storage.block_cache.evictions`): above 0 where the store is larger
than the budget and the cache turns over under load.

In `aggns-query-3d` the reads are spread over 3 days."""


def read(m):
    n = len(m.rec.get("status", ()))
    return m.moved("storage.block_cache.evictions") / n if n else None
