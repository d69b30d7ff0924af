"""How late the generator ran at depth: `loadgen_lag_p95_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "loadgen_lag_p95_ms")
