"""Block-cache entries evicted per request of the window where the reads are spread
over 3 days: `block_cache_evictions_per_query`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "block_cache_evictions_per_query")
