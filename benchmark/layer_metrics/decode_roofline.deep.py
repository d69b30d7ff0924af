"""The decode program's share of the chip's HBM roofline on one chip, where
the embedded read path's cold decodes and the cache's admissions run it
inside the window: `decode_roofline`'s reading (bytes from each call's own
HLO line, device time from its trace events)."""

from harness import spec

read = spec.load_reader("layer_metrics", "decode_roofline")
