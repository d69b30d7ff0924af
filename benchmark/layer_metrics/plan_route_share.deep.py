"""Share of queries on the compiled plan route at depth (a series over 12
hours is 4,320 cells, above the floor): `plan_route_share`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_route_share")
