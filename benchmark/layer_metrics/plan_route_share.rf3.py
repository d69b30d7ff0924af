"""Queries of the cluster cell that ran on the compiled plan route, of the
queries executed: `plan_route_share`'s reading, on the coordinator's
own device."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_route_share")
