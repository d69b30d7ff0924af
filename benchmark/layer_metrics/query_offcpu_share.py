"""Of the request threads' wall time (root spans, accept to last byte), the
part their threads were not on a CPU (root wall minus the `cpu_ns` tag):
waiting for the GIL, a lock, a socket or the device.

In `rf3-query-thin` that is mostly the wait for the fan-out's workers
and the nodes' handler threads, which share the one GIL. In
`promrw4k-mixed` it is over every request root, the fleet's
remote-writes too; the reads alone are `read_offcpu_share`."""

from harness import phases


def read(m):
    return phases.offcpu_share(phases.request_roots(m))
