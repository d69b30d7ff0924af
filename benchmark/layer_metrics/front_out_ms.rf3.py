"""The engine to the last byte in front of a cluster: `front_out_ms`'s
reading (query.execute_range's end to the request root's end) on the
dedicated coordinator."""

from harness import spec

read = spec.load_reader("layer_metrics", "front_out_ms")
