"""The long-range dashboard's tail: `query_tail_p95_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "query_tail_p95_ms")
