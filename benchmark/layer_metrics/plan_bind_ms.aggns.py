"""The compiled plan's bind (with the fetch it drives) over 1-minute points:
`plan_bind_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_bind_ms")
