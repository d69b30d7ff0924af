"""Self time of query.fetch per query over the 1-minute namespace: `fetch_ms_per_query`'s
reading (the resolve, the routed sweep over 6-7 two-hour blocks a series, cache
lookups, cold decode, merges)."""

from harness import spec

read = spec.load_reader("layer_metrics", "fetch_ms_per_query")
