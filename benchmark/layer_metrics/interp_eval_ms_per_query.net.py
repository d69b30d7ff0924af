"""`interp_eval_ms_per_query`'s reading in the cell of the `net` counters behind rate()
panels (`net4k-query-rate`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "interp_eval_ms_per_query")
