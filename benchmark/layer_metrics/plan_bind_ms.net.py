"""`plan_bind_ms`'s reading in the cell of the `net` counters behind rate()
panels (`net4k-query-rate`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_bind_ms")
