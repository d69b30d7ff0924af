"""storage.block_cache hits over lookups in the window, over an aggregated namespace
(2.77 GB of planes) larger than the cache's budget and read over 67 of its 72
hours: `block_cache_hit_share`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "block_cache_hit_share")
