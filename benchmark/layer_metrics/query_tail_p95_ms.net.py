"""`query_tail_p95_ms`'s reading in the cell of the `net` counters behind rate()
panels (`net4k-query-rate`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "query_tail_p95_ms")
