"""memory_stats()['peak_bytes_in_use'] at depth: `peak_hbm_bytes.query`'s
reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "peak_hbm_bytes.query")
