"""What a runnable thread waits for the GIL under eight senders, the
accepting thread and the tick: `gil_wait_p95_ms`'s reading."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "gil_wait_p95_ms")
