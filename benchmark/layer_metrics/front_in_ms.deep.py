"""Accept to the engine at depth: `front_in_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "front_in_ms")
