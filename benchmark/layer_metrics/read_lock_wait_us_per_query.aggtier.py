"""`read_lock_wait_us_per_query`'s reading in the cell of 12-hour panels read while the aggregation tier writes (`aggtier-query-live`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "read_lock_wait_us_per_query")
