"""1 minus the union of device-operation intervals over the traced window
at depth: `device_idle_share.query`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "device_idle_share.query")
