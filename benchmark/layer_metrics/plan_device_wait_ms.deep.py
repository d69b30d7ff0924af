"""Waiting for the device per compiled-route query at depth:
`plan_device_wait_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_device_wait_ms")
