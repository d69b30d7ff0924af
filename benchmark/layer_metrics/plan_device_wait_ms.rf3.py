"""Enqueue plus wait per compiled-route query on the coordinator's device:
`plan_device_wait_ms`'s reading in the cluster cell."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_device_wait_ms")
