"""Full collections inside the window over the window, in the one process
that holds the coordinator and all three nodes: `gc_pause_share`'s
reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "gc_pause_share")
