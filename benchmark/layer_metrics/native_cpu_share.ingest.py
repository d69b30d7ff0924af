"""Host CPU below Python in the ingest cell: `native_cpu_share`'s reading."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "native_cpu_share")
