"""Plan bind per compiled-route query of the cluster cell: `plan_bind_ms`'s
reading. The bind fetches its selectors, so here it holds those
queries' whole clustered fetch."""

from harness import spec

read = spec.load_reader("layer_metrics", "plan_bind_ms")
