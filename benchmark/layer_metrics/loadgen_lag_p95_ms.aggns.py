"""How late the generator sent what was due, with one request in flight over
the two-tier deployment: `loadgen_lag_p95_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "loadgen_lag_p95_ms")
