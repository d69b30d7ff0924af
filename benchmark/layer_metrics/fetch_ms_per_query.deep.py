"""Self time of query.fetch per query at depth: `fetch_ms_per_query`'s
reading (the routed sweep, cache lookups, cold decode, merges)."""

from harness import spec

read = spec.load_reader("layer_metrics", "fetch_ms_per_query")
