"""The longest stall inside the ingest window: `stall_max_ms`'s reading."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "stall_max_ms")
