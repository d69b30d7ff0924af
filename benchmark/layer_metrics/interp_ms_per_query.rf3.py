"""The interpreter over a clustered fetch: `interp_ms_per_query`'s reading
(self time of query.execute_range where the span says route=interpreter;
the session's fetch is a child span and is not in it)."""

from harness import spec

read = spec.load_reader("layer_metrics", "interp_ms_per_query")
