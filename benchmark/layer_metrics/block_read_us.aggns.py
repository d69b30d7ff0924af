"""One (series, block) read of a two-hour 1-minute block, mean: `block_read_us`'s
reading (`block_ns` over `block_n`: resolve, cache lookup, gather, cold decode)."""

from harness import spec

read = spec.load_reader("layer_metrics", "block_read_us")
