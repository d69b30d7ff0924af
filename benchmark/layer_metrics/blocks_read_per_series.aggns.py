"""Sealed blocks a fetched series was read from in the aggregated namespace, mean:
`blocks_read_per_series`'s reading (`block_n` over `series_n`: 6-7 two-hour blocks
behind a 12-hour range, where the 10 s namespace would read 36)."""

from harness import spec

read = spec.load_reader("layer_metrics", "blocks_read_per_series")
