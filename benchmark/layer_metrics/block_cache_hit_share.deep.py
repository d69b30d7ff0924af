"""storage.block_cache hits over lookups in the window, over a store larger
than the cache's budget: `block_cache_hit_share`'s reading, a (series,
block) row counting one."""

from harness import spec

read = spec.load_reader("layer_metrics", "block_cache_hit_share")
