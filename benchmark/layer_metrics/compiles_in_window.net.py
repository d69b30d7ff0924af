"""`compiles_in_window.query`'s reading in the cell of the `net` counters behind rate()
panels (`net4k-query-rate`)."""

from harness import spec

read = spec.load_reader("layer_metrics", "compiles_in_window.query")
