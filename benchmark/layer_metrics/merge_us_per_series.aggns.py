"""Making one run of a series' 6-7 parts of 120 one-minute points, mean:
`merge_us_per_series`'s reading (`merge_ns` over `series_n`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "merge_us_per_series")
