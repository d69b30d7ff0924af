"""Of the request threads' wall time (root spans, accept to last byte), the
part their threads were not on a CPU (root wall minus the `cpu_ns` tag):
waiting for the GIL, a lock, a socket or the device."""

from harness import phases


def read(m):
    return phases.offcpu_share(phases.request_roots(m))
