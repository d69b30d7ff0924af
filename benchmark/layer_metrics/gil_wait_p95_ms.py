"""What a thread that turns runnable waits for the GIL: p95 of the runtime
probe's wake lateness net of its own run-queue time, over the wakes due
inside the window (`host_cpu_busy_share` holds the reading)."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "gil_wait_p95_ms")
