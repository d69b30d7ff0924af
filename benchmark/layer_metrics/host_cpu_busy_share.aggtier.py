"""`host_cpu_busy_share`'s reading in the cell of 12-hour panels read while the aggregation tier writes (`aggtier-query-live`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "host_cpu_busy_share")
