"""XLA backend compiles inside the window of the 3-day panels; must read 0:
`compiles_in_window.query`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "compiles_in_window.query")
