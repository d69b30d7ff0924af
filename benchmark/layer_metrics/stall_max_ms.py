"""The longest stall the runtime probe recorded inside the window (a wake
more than 100 ms late), 0 when none; who held the CPU across each goes
to standard error (`host_cpu_busy_share` holds the reading)."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "stall_max_ms")
