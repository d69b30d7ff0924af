"""Host CPU the process spent in threads the interpreter never made (XLA's,
the TPU runtime's) over all of its CPU (`host_cpu_busy_share` holds the
reading)."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "native_cpu_share")
