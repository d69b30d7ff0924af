"""Collector pauses over the window at depth: `gc_pause_share`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "gc_pause_share")
