"""The index query over a range that overlaps one or two 24-hour index blocks of the
aggregated namespace: `index_query_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "index_query_ms")
