"""Plan bind per compiled-route query at depth (grids 12 times longer; the
bind holds the fetch): `plan_bind_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_bind_ms")
