"""Wall time the request threads spent off the CPU at depth:
`query_offcpu_share`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "query_offcpu_share")
