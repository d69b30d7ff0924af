"""The read of one series at depth: `fetch_us_per_series`'s reading
(query.fetch's `read_ns` over `series_n`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "fetch_us_per_series")
