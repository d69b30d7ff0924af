"""The index query over a range that overlaps three or four 4-hour index
blocks: `index_query_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "index_query_ms")
