"""Accept to the engine in front of a cluster: `front_in_ms`'s reading
(the request root's start to its query.execute_range span's start) on the
dedicated coordinator."""

from harness import spec

read = spec.load_reader("layer_metrics", "front_in_ms")
